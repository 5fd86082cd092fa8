"""Pipeline parallelism — GPipe microbatch schedule as a single SPMD program.

The reference has no pipeline parallelism (SURVEY.md §2c marks PP "out of
reference scope"), but a complete TPU framework needs it for models whose
layers don't fit one chip even under TP. This is the TPU-idiomatic design:
instead of per-stage processes passing activations over a transport (the
PS/worker shape), the per-stage parameters are *stacked* along a leading
``stage`` dimension sharded over the ``pipe`` mesh axis, and the whole
schedule — bubble included — is one ``lax.scan`` inside ``shard_map``:

- every scan step, each stage applies ``stage_fn`` to its current activation
  and ships the result one hop down the ring (``ppermute`` — a single ICI
  neighbor transfer, exactly the point-to-point the hardware is best at);
- stage 0 feeds microbatch ``t`` in at step ``t``; the last stage writes its
  result for microbatch ``t - (S-1)`` into an output buffer;
- the backward schedule needs no code: autodiff of scan+ppermute *is* the
  reverse pipeline (activations are rematerialized per ``jax.checkpoint``
  policy if the caller wraps ``stage_fn``).

Composes with the other axes: batch dims inside a microbatch stay sharded
over ``data`` (and ``seq``/``model`` inside ``stage_fn``), so dp x pp x tp is
one program. Bubble fraction is the usual (S-1)/(M+S-1); choose
``n_microbatches >= 4*n_stages`` to amortize.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dtf_tpu.core.comms import ring_perm, shift_perm
from dtf_tpu.core.mesh import AXIS_PIPE

PyTree = Any


def stack_stage_params(params_per_stage: list[PyTree]) -> PyTree:
    """Stack S per-stage param pytrees along a new leading stage dim."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_per_stage)


def init_stacked(init_fn: Callable[[jax.Array], PyTree], n_stages: int,
                 rng: jax.Array) -> PyTree:
    """Initialize S independent stage params, stacked: vmap(init) over rngs.

    The stacked tree is what gets sharded ``P('pipe', ...)`` — the successor
    of the reference's per-PS variable placement, with stages instead of
    parameter servers as the unit of distribution.
    """
    return jax.vmap(init_fn)(jax.random.split(rng, n_stages))


def pipeline_spmd(
    stage_fn: Callable[[PyTree, jax.Array], jax.Array],
    n_microbatches: int,
    mesh: Mesh,
    *,
    axis_name: str = AXIS_PIPE,
    batch_spec: P = P("data"),
    param_spec_fn: Callable[[Any], P] | None = None,
    param_specs_fn: Callable[[PyTree], PyTree] | None = None,
    check_vma: bool = True,
):
    """Build ``f(stacked_params, x) -> y`` running stages over ``axis_name``.

    ``stage_fn(stage_params, x) -> y`` maps one stage over one microbatch and
    must preserve the activation shape/dtype (the homogeneous-stack case —
    transformer blocks; put embedding/head outside the pipeline).

    ``x``: [B, ...] with B divisible by ``n_microbatches`` x data-shards.
    ``stacked_params``: leading dim = pipe-axis size (see
    :func:`init_stacked`), sharded ``P('pipe', ...)``.

    Returns a function usable under ``jit``; gradients flow through to the
    stacked params and the input.

    ``param_specs_fn``: full params→spec-TREE mapping (path-dependent specs,
    e.g. Megatron TP dims inside stages — see
    :mod:`dtf_tpu.models.gpt_pipe_tp`); overrides the leaf-wise
    ``param_spec_fn``. ``check_vma=False`` disables shard_map's
    varying-manual-axes typing for bodies that mix axes it cannot type
    (per-shard collectives inside the stage).
    """
    n_stages = mesh.shape.get(axis_name, 1)

    def sharded(params, x):
        if x.shape[0] % n_microbatches:
            raise ValueError(
                f"batch {x.shape[0]} not divisible by n_microbatches="
                f"{n_microbatches}")
        n_stacked = jax.tree.leaves(params)[0].shape[0]
        if n_stacked != n_stages:
            raise ValueError(
                f"stage stack has {n_stacked} stages but the '{axis_name}' "
                f"mesh axis has {n_stages} shards; they must match (each "
                "device runs exactly one stage)")
        if n_stages == 1:
            # degenerate pipe axis: plain application, no schedule.
            squeezed = jax.tree.map(lambda p: p[0], params)
            return stage_fn(squeezed, x)

        micro = x.reshape((n_microbatches, x.shape[0] // n_microbatches)
                          + x.shape[1:])

        def body(params, xs):
            # per-shard: params [1, ...] slice of the stage stack; xs
            # [M, mb/data, ...] microbatches (replicated over pipe).
            # pvary: xs arrives replicated over pipe but mixes with
            # pipe-varying values (stage outputs) below — shard_map's
            # varying-manual-axes type system requires the promotion to be
            # explicit. (Skipped when the caller disabled vma typing.)
            if check_vma:
                xs = jax.lax.pcast(xs, (axis_name,), to="varying")
            p = jax.tree.map(lambda t: t[0], params)
            idx = jax.lax.axis_index(axis_name)
            shift = shift_perm(n_stages)

            def step(carry, t):
                act, out = carry
                x_t = jax.lax.dynamic_index_in_dim(
                    xs, jnp.clip(t, 0, n_microbatches - 1), 0, keepdims=False)
                inp = jnp.where(idx == 0, x_t, act)
                y = stage_fn(p, inp)
                # ship to the next stage; stage S-1's y falls off the end
                # (shift is not a ring — no wraparound into stage 0).
                act = jax.lax.ppermute(y, axis_name, shift)
                ot = t - (n_stages - 1)
                ot_c = jnp.clip(ot, 0, n_microbatches - 1)
                write = (idx == n_stages - 1) & (ot >= 0)
                cur = jax.lax.dynamic_index_in_dim(out, ot_c, 0,
                                                   keepdims=False)
                out = jax.lax.dynamic_update_index_in_dim(
                    out, jnp.where(write, y, cur), ot_c, 0)
                return (act, out), None

            act0 = jnp.zeros_like(xs[0])
            out0 = jnp.zeros_like(xs)
            (_, out), _ = jax.lax.scan(
                step, (act0, out0), jnp.arange(n_microbatches + n_stages - 1))
            # outputs live on the last stage only (zeros elsewhere) —
            # replicate over the pipe axis with one psum.
            return jax.lax.psum(out, axis_name)

        if param_specs_fn is not None:
            p_spec = param_specs_fn(params)
        elif param_spec_fn is not None:
            p_spec = jax.tree.map(param_spec_fn, params)
        else:
            p_spec = stage_param_specs(params, axis_name)
        micro_spec = P(None, *batch_spec)
        y = jax.shard_map(
            body, mesh=mesh,
            in_specs=(p_spec, micro_spec), out_specs=micro_spec,
            check_vma=check_vma,
        )(params, micro)
        return y.reshape(x.shape[0:1] + y.shape[2:])

    return sharded


def stage_param_specs(params: PyTree, axis_name: str = AXIS_PIPE) -> PyTree:
    """P('pipe') spec tree for a stacked-stage param tree (for train-state
    sharding rules / create_train_state param_rules bypass)."""
    return jax.tree.map(lambda _: P(axis_name), params)


def _axes_of(spec: P) -> tuple[str, ...]:
    """Flatten a PartitionSpec into the mesh axis names it mentions."""
    axes: list[str] = []
    for part in spec:
        if part is None:
            continue
        if isinstance(part, str):
            axes.append(part)
        else:
            axes.extend(part)
    return tuple(axes)


def pipeline_1f1b_grads(
    first_fn: Callable[[PyTree, PyTree], jax.Array],
    stage_fn: Callable[[PyTree, jax.Array], jax.Array],
    last_fn: Callable[[PyTree, jax.Array, PyTree], tuple[jax.Array, jax.Array]],
    n_microbatches: int,
    mesh: Mesh,
    *,
    axis_name: str = AXIS_PIPE,
    batch_spec: P = P("data"),
    check_vma: bool = False,
):
    """1F1B-style fused forward/backward pipeline — O(S) activation stash.

    The GPipe/interleaved schedules above differentiate *through* the scan,
    so autodiff stashes residuals for ALL ``M`` microbatches before the first
    backward runs (the classic GPipe memory profile; ``jax.checkpoint`` on
    ``stage_fn`` shrinks each stash to the stage input but not their count).
    This schedule interleaves forwards and backwards in ONE scan so at most
    ``2S-2`` microbatches are ever in flight per stage — the 1F1B property —
    which means it cannot ride ``jax.grad``: it computes gradients itself
    (per-microbatch ``jax.vjp``, backward recomputes the stage forward from
    the stashed stage *input* — remat is built in) and returns them.

    Round schedule (device ``i`` of ``S``, microbatch ``m`` of ``M``): each
    scan round ``r`` has a forward sub-slot then a backward sub-slot, with a
    neighbor ``ppermute`` after each:

    - ``F(i, m)`` runs at round ``r = i + m`` (activations flow down one hop
      per round, exactly like :func:`pipeline_spmd`);
    - ``B(i, m)`` runs at round ``r = (2S-2-i) + m`` (cotangents flow back up
      one hop per round; the last stage's ``B(S-1, m)`` shares round
      ``S-1+m`` with its own ``F`` — loss + head run inside its backward).

    Consecutive stages are one round apart in both directions, every arrival
    is consumed the round it lands, and a stage's in-flight window
    ``r_B - r_F = 2S-2-2i`` bounds the stash. Total rounds ``M + 2S - 2`` —
    the same fill/drain bubble class as GPipe, at ~``S/M``-th the stash.

    ``first_fn(first_params, mb) -> x`` feeds stage 0 (e.g. embedding);
    ``last_fn(last_params, y, mb) -> (loss_sum, weight)`` consumes the final
    stage output (e.g. LM head + cross-entropy, returning the SUM over the
    microbatch plus its weight). The total loss is ``Σ loss_sum / Σ weight``
    and gradients are of exactly that scalar (weights must not depend on
    params), so results match ``jax.grad`` of the equivalent un-pipelined
    loss. Both run under the schedule: ``first_fn`` only on stage 0's F
    rounds, ``last_fn`` (forward + vjp) only on the last stage's B rounds.

    Returns ``f(first_params, stacked_params, last_params, batch) ->
    (loss_sum, weight, (d_first, d_stages, d_last))`` — gradient SUMS in
    f32; divide by ``weight`` for the gradient of the mean loss.
    ``batch`` is a pytree of ``[B, ...]`` arrays, ``B`` divisible by
    ``n_microbatches`` x the batch shards. Per-round branch predicates
    depend only on the pipe index, so in-branch collectives over other mesh
    axes (e.g. ring attention over ``seq`` inside ``stage_fn``) stay
    uniform within their groups — dp x pp x sp composes.
    """
    n_stages = mesh.shape.get(axis_name, 1)
    S, M = n_stages, n_microbatches
    reduce_axes = _axes_of(batch_spec)
    all_axes = (axis_name,) + reduce_axes

    def z32(p):
        return jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32), p)

    def add32(a, d):
        return jax.tree.map(lambda t, u: t + u.astype(jnp.float32), a, d)

    def f(p_first, p_stack, p_last, batch):
        b0 = jax.tree.leaves(batch)[0].shape[0]
        if b0 % M:
            raise ValueError(
                f"batch {b0} not divisible by n_microbatches={M}")
        n_stacked = jax.tree.leaves(p_stack)[0].shape[0]
        if n_stacked != S:
            raise ValueError(
                f"stage stack has {n_stacked} stages but the '{axis_name}' "
                f"mesh axis has {S} shards; they must match")
        micro = jax.tree.map(
            lambda x: x.reshape((M, x.shape[0] // M) + x.shape[1:]), batch)

        if S == 1:
            # degenerate pipe axis: plain per-microbatch value_and_grad,
            # summed — identical math, no schedule.
            def one(pf, ps, pl, mb):
                x = first_fn(pf, mb)
                y = stage_fn(jax.tree.map(lambda t: t[0], ps), x)
                return last_fn(pl, y, mb)

            def body(carry, mb):
                gf, gs, gl, ls, ws = carry
                (l, w), g = jax.value_and_grad(
                    one, argnums=(0, 1, 2), has_aux=True)(
                        p_first, p_stack, p_last, mb)
                return (add32(gf, g[0]), add32(gs, g[1]), add32(gl, g[2]),
                        ls + l, ws + w), None

            (gf, gs, gl, ls, ws), _ = jax.lax.scan(
                body, (z32(p_first), z32(p_stack), z32(p_last),
                       jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                micro)
            return ls, ws, (gf, gs, gl)

        C = 2 * S - 1          # stash slots; in-flight <= 2S-2 (see above)
        R = M + 2 * S - 2      # total rounds

        def body(p_first, p_stack, p_last, mb):
            p_stage = jax.tree.map(lambda t: t[0], p_stack)
            idx = jax.lax.axis_index(axis_name)
            down = shift_perm(S)
            up = shift_perm(S, shift=-1)
            mb0 = jax.tree.map(lambda t: t[0], mb)
            x_sd = jax.eval_shape(first_fn, p_first, mb0)
            act0 = jnp.zeros(x_sd.shape, x_sd.dtype)
            stash0 = jnp.zeros((C,) + x_sd.shape, x_sd.dtype)

            def pick(m):
                return jax.tree.map(
                    lambda t: jax.lax.dynamic_index_in_dim(
                        t, m, 0, keepdims=False), mb)

            def round_fn(carry, r):
                act, cot, stash, gf, gs, gl, ls, ws = carry
                m_f = r - idx
                f_on = (m_f >= 0) & (m_f < M)
                m_fc = jnp.clip(m_f, 0, M - 1)
                m_b = r - (2 * S - 2 - idx)
                b_on = (m_b >= 0) & (m_b < M)
                m_bc = jnp.clip(m_b, 0, M - 1)

                # Control-flow invariant: ``stage_fn`` may contain
                # collectives over OTHER mesh axes (ring/halo attention over
                # seq, psums over data inside the stage), and collectives
                # must never sit under pipe-varying `lax.cond` — the branch
                # assignment then differs across pipe ranks and the lowered
                # collective schedule corrupts values (observed on the CPU
                # sim). So the stage forward AND its vjp run UNCONDITIONALLY
                # every round — exactly like the GPipe schedule's bubble
                # ticks — with `where`-selected inputs, masked writes, and a
                # zeroed cotangent when inactive (vjp is linear in the
                # cotangent, so inactive grad contributions are exactly 0).
                # first_fn/last_fn stay under cond: they must be
                # collective-free (embedding lookup / head + local loss).

                # ---- forward sub-slot ----
                mb_f = pick(m_fc)
                x_in = jax.lax.cond(
                    idx == 0,
                    lambda: first_fn(p_first, mb_f).astype(act.dtype),
                    lambda: act)
                y = stage_fn(p_stage, x_in)
                cur = jax.lax.dynamic_index_in_dim(stash, m_fc % C, 0,
                                                   keepdims=False)
                stash = jax.lax.dynamic_update_index_in_dim(
                    stash, jnp.where(f_on, x_in, cur), m_fc % C, 0)
                act = jax.lax.ppermute(
                    jnp.where(f_on, y, jnp.zeros_like(y)), axis_name, down)

                # ---- backward sub-slot ----
                mb_b = pick(m_bc)
                x_b = jax.lax.dynamic_index_in_dim(stash, m_bc % C, 0,
                                                   keepdims=False)
                y2, svjp = jax.vjp(stage_fn, p_stage, x_b)

                def last_dy(_):
                    def lf(pl, yy):
                        return last_fn(pl, yy, mb_b)
                    l, lvjp, w = jax.vjp(lf, p_last, y2, has_aux=True)
                    seed = jnp.where(b_on, jnp.ones_like(l),
                                     jnp.zeros_like(l))
                    dpl, dy = lvjp(seed)
                    on = b_on.astype(jnp.float32)
                    return (dy.astype(y2.dtype), add32(gl, dpl),
                            ls + on * l.astype(jnp.float32),
                            ws + on * w.astype(jnp.float32))

                dy, gl, ls, ws = jax.lax.cond(
                    idx == S - 1, last_dy,
                    lambda _: (jnp.where(b_on, cot, jnp.zeros_like(cot)),
                               gl, ls, ws),
                    None)
                dps, dx = svjp(dy)
                gs = add32(gs, dps)

                def first_g(_):
                    _, fvjp = jax.vjp(lambda pf: first_fn(pf, mb_b),
                                      p_first)
                    (dpf,) = fvjp(dx.astype(x_sd.dtype))
                    return add32(gf, dpf)

                gf = jax.lax.cond(idx == 0, first_g, lambda _: gf, None)
                cot = jax.lax.ppermute(dx.astype(act.dtype), axis_name, up)
                return (act, cot, stash, gf, gs, gl, ls, ws), None

            init = (act0, jnp.zeros_like(act0), stash0,
                    z32(p_first), z32(p_stage), z32(p_last),
                    jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
            (_, _, _, gf, gs, gl, ls, ws), _ = jax.lax.scan(
                round_fn, init, jnp.arange(R))

            # grads/loss are partial sums: stage grads live on their own
            # pipe rank but are partial over the batch axes; first/last
            # grads and the loss live on one pipe rank AND are partial over
            # the batch axes.
            if reduce_axes:
                gs = jax.lax.psum(gs, reduce_axes)
            gf = jax.lax.psum(gf, all_axes)
            gl = jax.lax.psum(gl, all_axes)
            ls = jax.lax.psum(ls, all_axes)
            ws = jax.lax.psum(ws, all_axes)
            # re-stack the local stage-grad row so out_specs P(axis_name)
            # maps rows back to the stacked layout.
            gs = jax.tree.map(lambda t: t[None], gs)
            return ls, ws, gf, gs, gl

        micro_spec = P(None, *batch_spec)
        ls, ws, gf, gs, gl = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(axis_name), P(),
                      jax.tree.map(lambda _: micro_spec, batch)),
            out_specs=(P(), P(), P(), P(axis_name), P()),
            check_vma=check_vma,
        )(p_first, p_stack, p_last, micro)
        return ls, ws, (gf, gs, gl)

    return f


def pipeline_zb_grads(
    first_fn: Callable[[PyTree, PyTree], jax.Array],
    stage_fn: Callable[[PyTree, jax.Array], jax.Array],
    last_fn: Callable[[PyTree, jax.Array, PyTree], tuple],
    n_microbatches: int,
    mesh: Mesh,
    *,
    axis_name: str = AXIS_PIPE,
    batch_spec: P = P("data"),
    check_vma: bool = False,
):
    """Zero-bubble 1F1B: W/B-split backward, W scheduled into the bubble.

    Same contract, signature and schedule skeleton as
    :func:`pipeline_1f1b_grads`, but each microbatch's backward is split
    (the ZB-H1 move, arxiv 2412.14374):

    - ``B(i, m)`` — activation-grad only (``vjp`` w.r.t. the stage INPUT),
      on the critical path: the cotangent must reach stage ``i-1`` next
      round. Runs where 1F1B ran its fused backward, ``r = 2S-2-i + m``,
      and pushes ``dy`` into a depth-``S`` ring (the stage input is already
      in the 1F1B remat stash — slot ``m % C`` is not overwritten until
      round ``i + m + 2S-1``, after every consumer).
    - ``W(i, m)`` — weight-grad (``vjp`` w.r.t. the stage PARAMS with the
      stashed ``dy``), deferrable: nothing downstream consumes it until the
      end-of-step psum. It runs at ``r = 2S-2 + m`` — device ``i`` thereby
      defers exactly ``i`` W passes into its ``i`` post-drain idle rounds,
      so the last W lands on the last round and total rounds stay
      ``M + 2S-2``. The stash bound (``2S-1`` slots + the ``S``-deep dy
      ring) and the 1F1B <=2S-2-in-flight property are preserved.

    In the lockstep scan both sub-slots still execute every round (masked
    when idle — the collective-uniformity invariant below), so the CPU-sim
    wall clock does not shrink; the win is on the MPMD executor the
    schedule targets, where a device's W fills wall-clock holes between
    dependency-gated F/B ops (see :func:`schedule_bubble_model` for the
    step-count accounting; no cell times a pipelined step). On this
    remat-style path W re-runs the stage forward
    from the stashed input (same recompute class as 1F1B's fused
    backward, paid once more).

    Gradient accumulation order is pinned to 1F1B's: W contributions are
    popped FIFO (increasing ``m``), and idle-round contributions are exact
    zeros (vjp is linear in the cotangent), so on integer-valued data the
    returned grads are BITWISE equal to :func:`pipeline_1f1b_grads` —
    asserted in tests/test_pipeline.py.
    """
    n_stages = mesh.shape.get(axis_name, 1)
    if n_stages == 1:
        # degenerate pipe axis: no bubble to fill, no schedule — the 1F1B
        # per-microbatch value_and_grad scan is already fused and optimal.
        return pipeline_1f1b_grads(
            first_fn, stage_fn, last_fn, n_microbatches, mesh,
            axis_name=axis_name, batch_spec=batch_spec, check_vma=check_vma)
    S, M = n_stages, n_microbatches
    reduce_axes = _axes_of(batch_spec)
    all_axes = (axis_name,) + reduce_axes

    def z32(p):
        return jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32), p)

    def add32(a, d):
        return jax.tree.map(lambda t, u: t + u.astype(jnp.float32), a, d)

    def f(p_first, p_stack, p_last, batch):
        b0 = jax.tree.leaves(batch)[0].shape[0]
        if b0 % M:
            raise ValueError(
                f"batch {b0} not divisible by n_microbatches={M}")
        n_stacked = jax.tree.leaves(p_stack)[0].shape[0]
        if n_stacked != S:
            raise ValueError(
                f"stage stack has {n_stacked} stages but the '{axis_name}' "
                f"mesh axis has {S} shards; they must match")
        micro = jax.tree.map(
            lambda x: x.reshape((M, x.shape[0] // M) + x.shape[1:]), batch)

        C = 2 * S - 1          # stash slots; in-flight <= 2S-2 (1F1B bound)
        R = M + 2 * S - 2      # total rounds — unchanged by the W split

        def body(p_first, p_stack, p_last, mb):
            p_stage = jax.tree.map(lambda t: t[0], p_stack)
            idx = jax.lax.axis_index(axis_name)
            down = shift_perm(S)
            up = shift_perm(S, shift=-1)
            mb0 = jax.tree.map(lambda t: t[0], mb)
            x_sd = jax.eval_shape(first_fn, p_first, mb0)
            act0 = jnp.zeros(x_sd.shape, x_sd.dtype)
            stash0 = jnp.zeros((C,) + x_sd.shape, x_sd.dtype)
            dyq0 = jnp.zeros((S,) + x_sd.shape, x_sd.dtype)

            def pick(m):
                return jax.tree.map(
                    lambda t: jax.lax.dynamic_index_in_dim(
                        t, m, 0, keepdims=False), mb)

            def round_fn(carry, r):
                act, cot, stash, dyq, gf, gs, gl, ls, ws = carry
                m_f = r - idx
                f_on = (m_f >= 0) & (m_f < M)
                m_fc = jnp.clip(m_f, 0, M - 1)
                m_b = r - (2 * S - 2 - idx)
                b_on = (m_b >= 0) & (m_b < M)
                m_bc = jnp.clip(m_b, 0, M - 1)
                m_w = r - (2 * S - 2)
                w_on = (m_w >= 0) & (m_w < M)
                m_wc = jnp.clip(m_w, 0, M - 1)

                # Collective-uniformity invariant: exactly as in
                # pipeline_1f1b_grads, the stage forward, its B (input)
                # vjp and its W (param) vjp all run UNCONDITIONALLY every
                # round — masked inputs / masked stash writes / zeroed
                # cotangents — because stage_fn may contain collectives
                # over other mesh axes and those must never sit under a
                # pipe-varying lax.cond. first_fn/last_fn stay under cond
                # (collective-free by contract).

                # ---- forward sub-slot (identical to 1F1B) ----
                mb_f = pick(m_fc)
                x_in = jax.lax.cond(
                    idx == 0,
                    lambda: first_fn(p_first, mb_f).astype(act.dtype),
                    lambda: act)
                y = stage_fn(p_stage, x_in)
                cur = jax.lax.dynamic_index_in_dim(stash, m_fc % C, 0,
                                                   keepdims=False)
                stash = jax.lax.dynamic_update_index_in_dim(
                    stash, jnp.where(f_on, x_in, cur), m_fc % C, 0)
                act = jax.lax.ppermute(
                    jnp.where(f_on, y, jnp.zeros_like(y)), axis_name, down)

                # ---- B sub-slot: activation grad only ----
                mb_b = pick(m_bc)
                x_b = jax.lax.dynamic_index_in_dim(stash, m_bc % C, 0,
                                                   keepdims=False)
                y2, xvjp = jax.vjp(lambda xx: stage_fn(p_stage, xx), x_b)

                def last_dy(_):
                    def lf(pl, yy):
                        return last_fn(pl, yy, mb_b)
                    l, lvjp, w = jax.vjp(lf, p_last, y2, has_aux=True)
                    seed = jnp.where(b_on, jnp.ones_like(l),
                                     jnp.zeros_like(l))
                    dpl, dy = lvjp(seed)
                    on = b_on.astype(jnp.float32)
                    return (dy.astype(y2.dtype), add32(gl, dpl),
                            ls + on * l.astype(jnp.float32),
                            ws + on * w.astype(jnp.float32))

                dy, gl, ls, ws = jax.lax.cond(
                    idx == S - 1, last_dy,
                    lambda _: (jnp.where(b_on, cot, jnp.zeros_like(cot)),
                               gl, ls, ws),
                    None)
                (dx,) = xvjp(dy)
                # push dy for the deferred W pass; slot m % S is not
                # re-written until B(m+S) at round 2S-2-i+m+S, strictly
                # after W(m) pops it at round 2S-2+m (i <= S-1).
                qcur = jax.lax.dynamic_index_in_dim(dyq, m_bc % S, 0,
                                                    keepdims=False)
                dyq = jax.lax.dynamic_update_index_in_dim(
                    dyq, jnp.where(b_on, dy.astype(act.dtype), qcur),
                    m_bc % S, 0)

                def first_g(_):
                    _, fvjp = jax.vjp(lambda pf: first_fn(pf, mb_b),
                                      p_first)
                    (dpf,) = fvjp(dx.astype(x_sd.dtype))
                    return add32(gf, dpf)

                gf = jax.lax.cond(idx == 0, first_g, lambda _: gf, None)
                cot = jax.lax.ppermute(dx.astype(act.dtype), axis_name, up)

                # ---- W sub-slot: deferred weight grad, FIFO pop ----
                # stash slot m % C still holds the stage input (see
                # docstring); the forward is recomputed from it, exactly
                # the remat 1F1B's fused backward did.
                x_w = jax.lax.dynamic_index_in_dim(stash, m_wc % C, 0,
                                                   keepdims=False)
                dy_w = jax.lax.dynamic_index_in_dim(dyq, m_wc % S, 0,
                                                    keepdims=False)
                _, pvjp = jax.vjp(lambda q: stage_fn(q, x_w), p_stage)
                (dps,) = pvjp(jnp.where(w_on, dy_w, jnp.zeros_like(dy_w)))
                gs = add32(gs, dps)
                return (act, cot, stash, dyq, gf, gs, gl, ls, ws), None

            init = (act0, jnp.zeros_like(act0), stash0, dyq0,
                    z32(p_first), z32(p_stage), z32(p_last),
                    jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
            (_, _, _, _, gf, gs, gl, ls, ws), _ = jax.lax.scan(
                round_fn, init, jnp.arange(R))

            if reduce_axes:
                gs = jax.lax.psum(gs, reduce_axes)
            gf = jax.lax.psum(gf, all_axes)
            gl = jax.lax.psum(gl, all_axes)
            ls = jax.lax.psum(ls, all_axes)
            ws = jax.lax.psum(ws, all_axes)
            gs = jax.tree.map(lambda t: t[None], gs)
            return ls, ws, gf, gs, gl

        micro_spec = P(None, *batch_spec)
        ls, ws, gf, gs, gl = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(axis_name), P(),
                      jax.tree.map(lambda _: micro_spec, batch)),
            out_specs=(P(), P(), P(), P(axis_name), P()),
            check_vma=check_vma,
        )(p_first, p_stack, p_last, micro)
        return ls, ws, (gf, gs, gl)

    return f


def schedule_bubble_model(n_stages: int, n_microbatches: int,
                          schedule: str = "1f1b", *,
                          t_f: float = 1.0, t_b: float = 1.0,
                          t_w: float = 1.0) -> dict:
    """Step-count bubble model for the fused-1F1B vs zero-bubble schedules.

    Simulates the MPMD executor the schedules target: each device runs its
    op sequence in schedule order, an op starts when the device is free AND
    its cross-device dependency has finished (``F(i,m)`` after ``F(i-1,m)``;
    ``B(i,m)`` after ``B(i+1,m)``, with the last stage's after its own
    ``F``; ``W(i,m)`` after its own ``B(i,m)``). 1F1B's backward is one
    fused op of cost ``t_b + t_w``; ZB splits it and defers W off the
    critical path, which shrinks the fill/drain bubble from
    ``(S-1)(t_f+t_b+t_w)`` toward ``(S-1)(t_f+t_b-t_w)`` (ZB-H1). The
    lockstep ``lax.scan`` realisation cannot show this (every round waits
    for the slowest sub-slot fleet-wide); this model is the schedule's
    honest accounting and is asserted in tests.

    Returns ``{"makespan", "busy", "idle_frac", "bubble"}`` — ``busy`` is
    total work per device-timeline (the same for both schedules), so
    ``idle_frac = 1 - busy / (S * makespan)`` is directly comparable.
    """
    if schedule not in ("1f1b", "zb"):
        raise ValueError(f"unknown schedule {schedule!r}")
    S, M = n_stages, n_microbatches
    cost = {"F": t_f, "B": t_b, "W": t_w, "BW": t_b + t_w}

    def device_ops(i):
        evs = []
        for m in range(M):
            evs.append((i + m, 0, "F", m))
            if schedule == "1f1b":
                evs.append((2 * S - 2 - i + m, 1, "BW", m))
            else:
                evs.append((2 * S - 2 - i + m, 1, "B", m))
                evs.append((2 * S - 2 + m, 2, "W", m))
        evs.sort()
        return [(kind, m) for _, _, kind, m in evs]

    bk = "BW" if schedule == "1f1b" else "B"

    def dep(kind, i, m):
        if kind == "F":
            return ("F", i - 1, m) if i else None
        if kind == "W":
            return ("B", i, m)
        return ("F", i, m) if i == S - 1 else (bk, i + 1, m)

    ops = {i: device_ops(i) for i in range(S)}
    ptr = [0] * S
    avail = [0.0] * S
    done: dict[tuple, float] = {}
    while any(ptr[i] < len(ops[i]) for i in range(S)):
        progress = False
        for i in range(S):
            while ptr[i] < len(ops[i]):
                kind, m = ops[i][ptr[i]]
                d = dep(kind, i, m)
                if d is not None and d not in done:
                    break
                t0 = max(avail[i], done.get(d, 0.0))
                done[(kind, i, m)] = t0 + cost[kind]
                avail[i] = t0 + cost[kind]
                ptr[i] += 1
                progress = True
        if not progress:  # pragma: no cover - schedule bug guard
            raise RuntimeError("deadlock in schedule model")
    makespan = max(done.values())
    busy = M * (t_f + t_b + t_w)
    return {
        "schedule": schedule,
        "n_stages": S,
        "n_microbatches": M,
        "makespan": makespan,
        "busy": busy,
        "idle_frac": 1.0 - busy / makespan,
        "bubble": makespan - busy,
    }


def interleaved_stage_order(n_devices: int, v_per_device: int) -> list[int]:
    """Stack-row order for the interleaved schedule.

    Device ``i`` must hold logical stages ``{i, n+i, 2n+i, ...}`` (the
    Megatron interleaved assignment), but a P('pipe')-sharded stack gives
    each device CONTIGUOUS rows. So the stack is laid out device-major:
    row ``i*V + v`` holds logical stage ``v*n + i``. Returns that logical
    order; use :func:`reorder_stages` to permute a logically-ordered stack.
    """
    return [v * n_devices + i
            for i in range(n_devices) for v in range(v_per_device)]


def reorder_stages(stacked: PyTree, n_devices: int,
                   v_per_device: int) -> PyTree:
    """Permute a logically-ordered [S, ...] stack into interleaved layout."""
    import numpy as np

    order = np.asarray(interleaved_stage_order(n_devices, v_per_device))
    return jax.tree.map(lambda t: t[order], stacked)


def pipeline_interleaved(
    stage_fn: Callable[[PyTree, jax.Array], jax.Array],
    n_microbatches: int,
    mesh: Mesh,
    v_per_device: int,
    *,
    axis_name: str = AXIS_PIPE,
    batch_spec: P = P("data"),
    check_vma: bool = True,
):
    """Interleaved (circular) pipeline schedule — the Megatron-style
    bubble-reduction over :func:`pipeline_spmd`.

    Each device holds ``V = v_per_device`` model chunks (logical stage
    ``v*n + i`` for device ``i``; total S = n*V finer-grained stages), and
    the activation circles the device ring V times per microbatch. The
    schedule is closed-form: device ``i`` runs (microbatch m, chunk v) at
    tick ``t = i + (m mod n) + n*(v + V*(m//n))`` — a unique (m, v) per
    (i, t), so every device does exactly one chunk per tick in steady state
    and the fill/drain bubble shrinks from (n-1)/M to ~(n-1)/(V*M) of total
    work at the cost of V x more ppermute hops (cheap: neighbor ICI).

    ``stacked_params``: [n*V, ...] in INTERLEAVED row order (see
    :func:`reorder_stages`), sharded P('pipe'). ``n_microbatches`` must be
    a multiple of the pipe-axis size. Gradients flow through scan+ppermute
    like the GPipe path; wrap ``stage_fn`` in ``jax.checkpoint`` to trade
    recompute for activation memory.
    """
    n_stages = mesh.shape.get(axis_name, 1)
    V = v_per_device

    def sharded(params, x):
        if x.shape[0] % n_microbatches:
            raise ValueError(
                f"batch {x.shape[0]} not divisible by n_microbatches="
                f"{n_microbatches}")
        if n_microbatches % max(n_stages, 1):
            raise ValueError(
                f"n_microbatches={n_microbatches} must be a multiple of the "
                f"'{axis_name}' axis size {n_stages} for the interleaved "
                "schedule")
        n_stacked = jax.tree.leaves(params)[0].shape[0]
        if n_stacked != n_stages * V:
            raise ValueError(
                f"stage stack has {n_stacked} rows but needs "
                f"{n_stages} devices x {V} chunks = {n_stages * V}")
        if n_stages == 1:
            out = x
            for v in range(V):
                out = stage_fn(jax.tree.map(lambda t: t[v], params), out)
            return out

        m_count = n_microbatches
        micro = x.reshape((m_count, x.shape[0] // m_count) + x.shape[1:])
        total_ticks = ((n_stages - 1) + ((m_count - 1) % n_stages)
                       + n_stages * ((V - 1) + V * ((m_count - 1)
                                                    // n_stages)) + 1)

        def body(params, xs):
            if check_vma:
                xs = jax.lax.pcast(xs, (axis_name,), to="varying")
            p_local = jax.tree.map(lambda t: t, params)   # [V, ...] shard
            idx = jax.lax.axis_index(axis_name)
            ring = ring_perm(n_stages)

            def step(carry, t):
                act, out = carry
                # closed-form schedule decode for (this device, tick t)
                u = t - idx
                active = u >= 0
                uc = jnp.maximum(u, 0)
                m_mod = uc % n_stages
                w = uc // n_stages           # = v + V * group
                v = w % V
                g = w // V
                m = g * n_stages + m_mod
                active = active & (m < m_count)
                m_c = jnp.clip(m, 0, m_count - 1)

                x_t = jax.lax.dynamic_index_in_dim(xs, m_c, 0,
                                                   keepdims=False)
                inp = jnp.where((idx == 0) & (v == 0), x_t, act)
                stage_p = jax.tree.map(
                    lambda t_: jax.lax.dynamic_index_in_dim(
                        t_, v, 0, keepdims=False), p_local)
                y = stage_fn(stage_p, inp)

                # final-chunk output on the last device → result buffer
                write = active & (idx == n_stages - 1) & (v == V - 1)
                cur = jax.lax.dynamic_index_in_dim(out, m_c, 0,
                                                   keepdims=False)
                out = jax.lax.dynamic_update_index_in_dim(
                    out, jnp.where(write, y, cur), m_c, 0)
                # everything rides the wraparound ring; the receiver's
                # schedule decode tells it whether the arrival is live
                act = jax.lax.ppermute(y, axis_name, ring)
                return (act, out), None

            act0 = jnp.zeros_like(xs[0])
            out0 = jnp.zeros_like(xs)
            (_, out), _ = jax.lax.scan(step, (act0, out0),
                                       jnp.arange(total_ticks))
            # result lives on the last device only; replicate over pipe.
            # psum would double-count nothing (zeros elsewhere).
            return jax.lax.psum(out, axis_name)

        p_spec = stage_param_specs(params, axis_name)
        micro_spec = P(None, *batch_spec)
        y = jax.shard_map(
            body, mesh=mesh,
            in_specs=(p_spec, micro_spec), out_specs=micro_spec,
            check_vma=check_vma,
        )(params, micro)
        return y.reshape(x.shape[0:1] + y.shape[2:])

    return sharded
