"""The pjit'd train step — successor of the reference's entire L2 session layer.

Reference capabilities replaced (SURVEY.md §3.1, §3.3):

- ``SyncReplicasOptimizer`` (TF ``sync_replicas_optimizer.py``): accumulate N
  worker gradients in PS-side ``ConditionalAccumulator``s, chief applies the
  *mean*, token queue releases workers. Here the same numerics — gradient =
  mean over the global batch — fall out of one compiled step: the batch is
  sharded over the ``data`` axis, the loss is a global mean, and XLA inserts
  the ICI all-reduce. Stale gradients cannot exist by construction; effective
  batch = global batch (= replicas × per-replica batch, as in the reference).
- Async-PS mode (``--issync=0``): intentionally racy hogwild updates. Not
  reproduced — synchronous SPMD is the semantic successor (behavioral delta
  documented in README).
- Gradient accumulation + ZeRO-1 (BASELINE config 4): microbatch scan in f32
  with optimizer state sharded over ``data`` (weight-update sharding).

Design: everything here is *one* jitted function over global arrays; the
ps/worker distinction, variable reads, and gradient pushes of the reference
are all inside XLA's partitioned program, riding ICI instead of gRPC.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dtf_tpu.core import executor
from dtf_tpu.core import sharding as shd
from dtf_tpu.core.comms import (batch_sharding, global_norm,
                                grad_reduce_scatter, shard_grads,
                                unshard_params)

PyTree = Any
#: loss_fn(params, extra, batch, rng) -> (loss, LossAux)
LossFn = Callable[..., tuple[jax.Array, "LossAux"]]


class LossAux(struct.PyTreeNode):
    """What a loss function returns besides the scalar loss.

    ``extra``: updated mutable collections (e.g. flax ``batch_stats``) — pass
    through unchanged if unused. ``metrics``: scalar diagnostics,
    weight-averaged across microbatches. ``weight``: this batch's
    contribution weight under
    gradient accumulation — losses that normalize by a data-dependent count
    (e.g. MLM valid positions) must return that count here so microbatch
    gradients combine as Σwᵢgᵢ/Σwᵢ (== the full-batch gradient) instead of a
    uniform mean.
    """

    extra: PyTree = struct.field(default_factory=dict)
    metrics: Mapping[str, jax.Array] = struct.field(default_factory=dict)
    weight: jax.Array | float = 1.0


class TrainState(struct.PyTreeNode):
    """Replicated-by-name successor of the reference's PS-resident state.

    The reference kept (variables, optimizer slots, global_step) on parameter
    servers; here they are one pytree, sharded by ``NamedSharding``, donated
    through the step. ``rng`` seeds per-step dropout etc. via fold_in(step).
    """

    step: jax.Array
    params: PyTree
    opt_state: PyTree
    extra: PyTree
    rng: jax.Array


@dataclasses.dataclass(frozen=True)
class StateShardings:
    """NamedSharding pytree matching TrainState, for jit in/out shardings."""

    state: TrainState  # of NamedShardings

    def batch(self, mesh: Mesh) -> NamedSharding:
        return batch_sharding(mesh)


def state_specs(
    init_fn: Callable[[jax.Array], PyTree],
    tx: optax.GradientTransformation,
    rng: jax.Array,
    mesh: Mesh,
    param_rules: Sequence[shd.Rule] = (),
    *,
    zero1: bool = True,
) -> TrainState:
    """PartitionSpec pytree (as a TrainState) for the full training state.

    ``init_fn(rng)`` must return the flax-style variables dict
    (``{"params": ..., [other collections...]}``).
    """
    abstract = jax.eval_shape(init_fn, rng)
    params = abstract["params"]
    extra = {k: v for k, v in abstract.items() if k != "params"}
    param_specs = shd.tree_specs(params, param_rules)
    if zero1:
        opt_specs = shd.zero1_opt_specs(tx, params, param_specs, mesh)
    else:
        opt_specs = shd.opt_specs_like_params(tx, params, param_specs)
    # Mutable collections (batch_stats) are small; replicate them.
    extra_specs = jax.tree.map(lambda _: P(), extra)
    return TrainState(step=P(), params=param_specs, opt_state=opt_specs,
                      extra=extra_specs, rng=P())


def state_shardings_from_specs(specs: TrainState, mesh: Mesh) -> TrainState:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _full_init(init_fn: Callable[[jax.Array], PyTree],
               tx: optax.GradientTransformation) -> Callable:
    """rng -> TrainState builder shared by real and abstract construction."""

    def init(rng):
        variables = init_fn(rng)
        params = variables["params"]
        extra = {k: v for k, v in variables.items() if k != "params"}
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
            extra=extra,
            rng=rng,
        )

    return init


def create_train_state(
    init_fn: Callable[[jax.Array], PyTree],
    tx: optax.GradientTransformation,
    rng: jax.Array,
    mesh: Mesh,
    param_rules: Sequence[shd.Rule] = (),
    *,
    zero1: bool = True,
) -> tuple[TrainState, TrainState]:
    """Initialize a sharded TrainState directly on the mesh.

    Returns ``(state, shardings)``. Parameters materialize already sharded
    (init is jitted with out_shardings), so no host-side full copy exists —
    the moment the reference handled with chief-init + PS placement.
    """
    specs = state_specs(init_fn, tx, rng, mesh, param_rules, zero1=zero1)
    shardings = state_shardings_from_specs(specs, mesh)
    state = jax.jit(_full_init(init_fn, tx), out_shardings=shardings)(rng)
    return state, shardings


def abstract_train_state(
    init_fn: Callable[[jax.Array], PyTree],
    tx: optax.GradientTransformation,
    rng: jax.Array,
    mesh: Mesh,
    param_rules: Sequence[shd.Rule] = (),
    *,
    zero1: bool = True,
) -> tuple[TrainState, TrainState]:
    """:func:`create_train_state` without touching a device.

    Returns ``(abstract_state, shardings)`` where the state's leaves are
    ``jax.ShapeDtypeStruct``s — exactly what AOT lowering
    (``step.lower(abstract_state, abstract_batch)``) and the static
    analyzer (:mod:`dtf_tpu.analysis`) need: the compiled collective mix
    can be inspected with zero device memory or compute for the state.
    """
    specs = state_specs(init_fn, tx, rng, mesh, param_rules, zero1=zero1)
    shardings = state_shardings_from_specs(specs, mesh)
    abstract = jax.eval_shape(_full_init(init_fn, tx), rng)
    return abstract, shardings


def make_train_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    shardings: TrainState,
    *,
    grad_accum: int = 1,
    grad_shard: bool = False,
    compute_dtype: jnp.dtype | None = None,
    log_grad_norm: bool = True,
    donate: bool = True,
    batch_shardings: PyTree | None = None,
    telemetry=None,
):
    """Build the compiled train step.

    ``loss_fn(params, extra, batch, rng) -> (loss, LossAux)`` computes the
    *mean* loss over its (global) batch — with the batch sharded over ``data``
    the resulting gradient is the mean over all replicas, which is exactly
    ``SyncReplicasOptimizer``'s aggregation semantics (SURVEY.md §3.3).

    ``grad_accum > 1``: the leading batch dim is split into ``grad_accum``
    microbatches scanned with ``lax.scan``, gradients accumulated in f32
    (BASELINE BERT config) as Σwᵢgᵢ/Σwᵢ with wᵢ = ``LossAux.weight`` (1.0 by
    default, giving the plain mean; count-normalized losses return their
    valid count so the result equals the full-batch gradient exactly).
    Loss and metrics combine with the same weights.

    ``grad_shard`` (with ``grad_accum > 1`` and a data axis > 1): ZeRO-1
    weight-update sharding for the accumulator (docs/ZERO.md). Each
    microbatch is split into its per-data-shard row groups (a vmapped
    loss call whose per-group gradients contract only over local rows, so
    nothing is reduced prematurely), the weighted per-group gradients are
    reduce-scattered over ``data`` into a 1/N-sized f32 shard accumulator
    inside the scan (the ``comms.grad_reduce_scatter`` choke point — half
    the bytes of the full all-reduce the replicated path issues per
    microbatch, overlapping the next microbatch's compute), the optimizer
    update runs on the gradient/param shard against the already-sharded
    ZeRO-1 optimizer state, and updated params are all-gathered back to
    their rulebook layout once per step (``comms.unshard_params``).
    Numerics are exact: the Σwᵢgᵢ/Σwᵢ weighting composes over the finer
    shard×microbatch grid (per-group count weights combine to the same
    full-batch gradient — bitwise on integer data); only the per-group
    dropout rng assignment differs (``fold_in(mb_rng, group)`` instead of
    one global mask per microbatch). Falls back to the replicated
    accumulator when ``data == 1``, when mutable collections are in play
    (``extra`` leaves cannot thread through shard-stacked loss calls),
    and per-leaf for params with no data-divisible dim.
    """

    def grads_of(params, extra, micro, rng):
        if compute_dtype is not None:
            micro = jax.tree.map(
                lambda x: x.astype(compute_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, micro)
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, extra, micro, rng)
        return loss, aux, grads

    param_specs = jax.tree.map(lambda s: s.spec, shardings.params)

    def step_fn(state: TrainState, batch: PyTree) -> tuple[TrainState, dict]:
        rng = jax.random.fold_in(state.rng, state.step)
        # set on the sharded-accumulator path; gates the shard-domain
        # optimizer update + the closing param all-gather below.
        shard_specs = None

        if grad_accum == 1:
            loss, aux, grads = grads_of(state.params, state.extra, batch, rng)
            metrics = dict(aux.metrics)
            extra = aux.extra
        else:
            data_size = mesh.shape.get("data", 1)
            # sharded-accumulation viability: a real data axis, and no
            # mutable collections — the per-shard-group loss calls each
            # produce their own `extra`, which cannot be threaded back
            # into one carry. The replicated path below stays bit-exact
            # with today's behavior whenever this is False.
            if (grad_shard and data_size > 1
                    and not jax.tree.leaves(state.extra)):
                shard_specs = shd.zero1_param_shard_specs(
                    state.params, param_specs, mesh)

            def to_micro(x, sh=None):
                if x.shape[0] % grad_accum or (
                        x.shape[0] // grad_accum) % data_size:
                    raise ValueError(
                        f"global batch {x.shape[0]} with grad_accum="
                        f"{grad_accum} gives microbatch "
                        f"{x.shape[0] // grad_accum}, which must be divisible "
                        f"by the data axis ({data_size} shards)")
                # scan (microbatch) axis replicated; the remaining dims keep
                # the leaf's batch sharding (e.g. P('data','seq') token ids
                # stay seq-sharded — hardcoding None here would all-gather
                # the sequence and defeat context parallelism).
                spec = tuple(sh.spec) if sh is not None else ("data",)
                spec = spec + (None,) * (x.ndim - len(spec))
                m = x.shape[0] // grad_accum
                if shard_specs is not None:
                    # split each microbatch into its per-data-shard row
                    # groups: [accum, n_data, rows/shard, ...], group axis
                    # on `data` so slot k IS shard k's local rows.
                    y = x.reshape(
                        (grad_accum, data_size, m // data_size) + x.shape[1:])
                    full = P(None, "data", None, *spec[1:])
                else:
                    y = x.reshape((grad_accum, m) + x.shape[1:])
                    full = P(None, *spec)
                return jax.lax.with_sharding_constraint(
                    y, NamedSharding(mesh, full))

            if batch_shardings is None:
                micro = jax.tree.map(to_micro, batch)
            else:
                micro = jax.tree.map(to_micro, batch, batch_shardings)

            def body(carry, mb):
                acc, w_sum, extra, i = carry
                mb_rng = jax.random.fold_in(rng, i)
                if shard_specs is not None:
                    # per-shard-group gradients: each vmap slot contracts
                    # only over its own (local) rows, so slot k holds
                    # shard k's UNREDUCED partial — the value the explicit
                    # reduce-scatter below sums and scatters in one
                    # collective. Σwᵢgᵢ/Σwᵢ runs over the finer
                    # group×microbatch grid, which combines to exactly the
                    # full-batch gradient (weights are per-group counts).
                    loss, aux, grads = jax.vmap(
                        lambda mb_k, k: grads_of(
                            state.params, extra, mb_k,
                            jax.random.fold_in(mb_rng, k)))(
                        mb, jnp.arange(data_size))
                    w = jnp.broadcast_to(
                        jnp.asarray(aux.weight, jnp.float32), (data_size,))
                    # a group whose weight is 0 (e.g. no masked MLM
                    # positions among its rows) may carry a 0/0 loss and
                    # NaN gradients from the loss's own count
                    # normalization; its Σwᵢgᵢ/Σwᵢ contribution is exactly
                    # zero either way, so select — don't multiply — it out
                    # (0·NaN would poison the accumulator).
                    def wmul(v):
                        wb = w[(...,) + (None,) * (v.ndim - 1)]
                        return jnp.where(wb > 0, v.astype(jnp.float32) * wb,
                                         0.0)

                    acc = jax.tree.map(
                        lambda a, r: a + r,
                        acc, grad_reduce_scatter(
                            jax.tree.map(wmul, grads), mesh, param_specs,
                            shard_specs))
                    # emit PRE-weighted per-microbatch sums; the post-scan
                    # combine divides the stacked sums by w_sum directly.
                    return ((acc, w_sum + w.sum(), extra, i + 1),
                            (wmul(loss).sum(), w.sum(),
                             jax.tree.map(lambda m: wmul(m).sum(),
                                          aux.metrics)))
                loss, aux, grads = grads_of(state.params, extra, mb, mb_rng)
                w = jnp.asarray(aux.weight, jnp.float32)
                acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) * w, acc, grads)
                return ((acc, w_sum + w, aux.extra, i + 1),
                        (loss * w, w, aux.metrics))

            acc0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            if shard_specs is not None:
                acc0 = shard_grads(acc0, mesh, shard_specs)
            (grads, w_sum, extra, _), (losses, ws, metric_seq) = jax.lax.scan(
                body,
                (acc0, jnp.zeros((), jnp.float32), state.extra,
                 jnp.zeros((), jnp.int32)),
                micro)
            grads = jax.tree.map(
                lambda g, p: (g / w_sum).astype(p.dtype), grads, state.params)
            if shard_specs is not None:
                grads = shard_grads(grads, mesh, shard_specs)
            loss = losses.sum() / w_sum
            # sharded path stacks PRE-weighted metric sums (see body);
            # replicated path stacks raw per-microbatch means.
            metrics = jax.tree.map(
                lambda m: (m if shard_specs is not None
                           else m * ws).sum() / w_sum, dict(metric_seq))

        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        if shard_specs is not None:
            # keep the update math in the shard domain (1/N of the
            # elementwise optimizer FLOPs per replica, against the
            # already-sharded ZeRO-1 moments) ...
            updates = shard_grads(updates, mesh, shard_specs)
        new_params = optax.apply_updates(state.params, updates)
        if shard_specs is not None:
            # ... and close with the ONE param all-gather per step.
            new_params = unshard_params(new_params, mesh, param_specs)
        metrics["loss"] = loss
        if log_grad_norm:
            metrics["grad_norm"] = global_norm(grads)
        new_state = state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt,
            extra=extra)
        return new_state, metrics

    # batch_shardings: a full pytree (from comms.batch_shardings_for) when
    # leaves need rank-dependent specs (e.g. P('data','seq') for [B,T] token
    # ids but P('data') for [B] labels); default is the P('data') prefix.
    batch_sh = (batch_shardings if batch_shardings is not None
                else batch_sharding(mesh))
    if telemetry is not None:
        # the wrapped body runs once per TRACE (not per call): the compile
        # fence pins Trainer.trace_counts["train_step"] at 1 in steady
        # state, the DecodeEngine.trace_counts contract for training.
        step_fn = telemetry.count_traces("train_step", step_fn)
    return executor.program(
        "train_step", step_fn, donate=donate,
        jit_kw=dict(in_shardings=(shardings, batch_sh),
                    out_shardings=(shardings, NamedSharding(mesh, P()))),
        arg_shardings=(shardings, batch_sh),
    )


def make_train_step_from_grads(
    grads_fn: Callable[..., tuple[jax.Array, "LossAux", PyTree]],
    tx: optax.GradientTransformation,
    mesh: Mesh,
    shardings: TrainState,
    *,
    log_grad_norm: bool = True,
    donate: bool = True,
    batch_shardings: PyTree | None = None,
    telemetry=None,
):
    """Train step for losses that produce their own gradients.

    ``grads_fn(params, extra, batch, rng) -> (loss, LossAux, grads)`` with
    ``grads`` matching the params tree — for paths where ``jax.grad`` over
    the loss would destroy the schedule the gradients must be computed
    under, e.g. the fused-1F1B pipeline
    (:func:`dtf_tpu.parallel.pipeline.pipeline_1f1b_grads`), whose O(S)
    activation stash only exists because forward and backward interleave in
    one scan. Microbatching lives inside such a ``grads_fn``, so there is
    no ``grad_accum`` here; optimizer update and metrics handling are
    identical to :func:`make_train_step`.
    """

    def step_fn(state: TrainState, batch: PyTree) -> tuple[TrainState, dict]:
        rng = jax.random.fold_in(state.rng, state.step)
        loss, aux, grads = grads_fn(state.params, state.extra, batch, rng)
        metrics = dict(aux.metrics)
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        metrics["loss"] = loss
        if log_grad_norm:
            metrics["grad_norm"] = global_norm(grads)
        new_state = state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt,
            extra=aux.extra)
        return new_state, metrics

    batch_sh = (batch_shardings if batch_shardings is not None
                else batch_sharding(mesh))
    if telemetry is not None:
        # same retrace fence as make_train_step (one program name: the
        # trainer runs exactly one step program either way)
        step_fn = telemetry.count_traces("train_step", step_fn)
    # same executor routing (and donation gate) as make_train_step.
    return executor.program(
        "train_step", step_fn, donate=donate,
        jit_kw=dict(in_shardings=(shardings, batch_sh),
                    out_shardings=(shardings, NamedSharding(mesh, P()))),
        arg_shardings=(shardings, batch_sh),
    )


def make_eval_step(eval_fn: Callable, mesh: Mesh, shardings: TrainState, *,
                   batch_shardings: PyTree | None = None, telemetry=None):
    """Compiled eval step: ``eval_fn(params, extra, batch) -> metrics dict``.

    ``batch_shardings``: override the default data-axis batch placement —
    REQUIRED under sequence parallelism (P('data','seq') batches), exactly
    like ``make_train_step``'s parameter of the same name; a committed
    input whose sharding disagrees with in_shardings makes jit raise.
    """

    def step_fn(state: TrainState, batch: PyTree):
        return eval_fn(state.params, state.extra, batch)

    if telemetry is not None:
        step_fn = telemetry.count_traces("eval_step", step_fn)
    # `is not None`, not truthiness: a falsy-but-valid shardings pytree
    # must not silently degrade to the default placement (same rule as
    # make_train_step's parameter of this name).
    batch_sh = (batch_shardings if batch_shardings is not None
                else batch_sharding(mesh))
    return executor.program(
        "eval_step", step_fn,
        jit_kw=dict(in_shardings=(shardings, batch_sh),
                    out_shardings=NamedSharding(mesh, P())),
        arg_shardings=(shardings, batch_sh),
    )
