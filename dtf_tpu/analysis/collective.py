"""Collective soundness — a dataflow pass over ``shard_map`` bodies.

The jaxpr lints (``analysis/jaxpr.py``) stop at the shard_map boundary:
collectives inside are "the whole point" and stay unexamined. But the
hand-written collectives the framework now leans on — the ppermute rings
of ``ops/collective_matmul.py``, ring/zigzag/halo attention, the pipeline
schedules, ``core/comms.grad_reduce_scatter`` — are exactly where a
transposed ``perm`` entry or a forgotten ``psum`` over a contracted axis
compiles cleanly and trains silently wrong. This pass walks every
shard_map body in the traced step and verifies:

- ``ppermute-not-permutation`` — a ``perm`` with an out-of-range index, a
  duplicated destination (nondeterministic overwrite) or a duplicated
  source. Partial shifts (halo exchange, pipeline edges — unique pairs,
  edges falling off) are legal; duplicates never are.
- ``unknown-collective-axis`` — a collective bound over an axis name the
  enclosing shard_map's mesh does not carry (it would resolve against
  whatever axis happens to be in scope, never what the rulebook meant).
- ``unreduced-partial-escape`` — a shard_map output derived from math
  that contracted a SHARDED dimension (a per-shard partial sum) escaping
  while its out_spec claims the value complete over the contracted axis
  (the axis appears nowhere in the output's ``out_names`` — with
  ``check_vma=False`` nothing else ever checks that claim). ``psum`` /
  ``pmean`` / ``psum_scatter`` discharge the obligation; riding a
  ``ppermute`` over the axis also exempts (a partial on a ring is being
  hand-reduced — the ring schedule itself is covered by
  :func:`ring_soundness` plus the bitwise parity tests, which a static
  pass cannot replace), and so does escaping SHARDED over the axis
  (per-shard partials handed to an outer reducer, e.g. autodiff
  residuals re-entering the mirrored backward shard_map). A partial
  that claims replication with no collective over its axis is the
  train-silently-wrong class this check exists for.

Separately, :func:`ring_soundness` holds every registered custom_vjp ring
pair (``ops/collective_matmul.ring_inventory``) to the mirrored-ring
invariant: both sides bind only true ring permutations, and the backward
rides the forward's ring or its exact inverse — anything else breaks the
overlap-under-grad contract PR 2's collective matmul depends on.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import numpy as np
from jax.extend import core as jex_core
from jax.sharding import Mesh, PartitionSpec as P

from dtf_tpu.analysis.findings import Finding
from dtf_tpu.analysis.jaxpr import _sub_jaxprs

#: collectives that discharge a partial-sum obligation over their axes
#: (``psum_invariant`` is what ``psum`` binds under a vma-checked
#: shard_map, and what its transpose rule inserts for a replicated input).
_REDUCING = frozenset({"psum", "psum_invariant", "pmean", "psum_scatter",
                       "reduce_scatter"})

#: collectives whose axis names must exist in the enclosing mesh.
_AXIS_COLLECTIVES = frozenset({
    "psum", "psum_invariant", "pmean", "pmax", "pmin", "ppermute",
    "pbroadcast", "pvary", "pgather", "all_gather", "all_gather_invariant",
    "all_to_all", "psum_scatter", "reduce_scatter",
})

#: primitives through which per-dim sharding tracking survives untouched.
_DIM_PRESERVING = frozenset({
    "convert_element_type", "copy", "integer_pow", "exp", "log", "tanh",
    "sqrt", "rsqrt", "neg", "sign", "abs", "floor", "ceil", "round",
    "is_finite", "logistic", "erf", "sin", "cos", "stop_gradient",
    "slice", "rev", "reduce_precision", "clamp",
})

#: binary/n-ary elementwise primitives (same-shape merge of records).
_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "max", "min", "pow", "rem", "atan2",
    "and", "or", "xor", "eq", "ne", "lt", "le", "gt", "ge", "select_n",
    "nextafter", "add_any",
})

#: reduction primitives (params['axes'] = reduced positional dims).
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_prod", "reduce_max", "reduce_min", "reduce_and",
    "reduce_or", "argmax", "argmin",
})


class _Rec(NamedTuple):
    """Abstract value: per-dim mesh axes (how this dim is sharded), the
    axes over which the value is an unreduced partial sum, and the axes
    whose ppermutes the value's ancestry has ridden."""

    dims: tuple          # tuple[frozenset[str], ...] aligned to rank
    partial: frozenset   # axes needing a reduction before escape
    ringed: frozenset    # axes whose ring the value has ridden

    @staticmethod
    def empty(rank: int = 0) -> "_Rec":
        return _Rec((frozenset(),) * rank, frozenset(), frozenset())


def _rank(var) -> int:
    return len(getattr(getattr(var, "aval", None), "shape", ()))


def _axes_of(params: dict) -> tuple[str, ...]:
    """Normalize a collective eqn's axis names to a flat tuple of strs."""
    raw = params.get("axes", params.get("axis_name", ()))
    if raw is None:
        return ()
    if isinstance(raw, str):
        return (raw,)
    out = []
    for a in (raw if isinstance(raw, (tuple, list)) else (raw,)):
        if isinstance(a, (tuple, list)):
            out.extend(str(x) for x in a)
        else:
            out.append(str(a))
    return tuple(out)


def _merge_dims(recs: list[_Rec], rank: int) -> tuple:
    dims = [frozenset()] * rank
    for r in recs:
        if len(r.dims) == rank:
            dims = [d | rd for d, rd in zip(dims, r.dims)]
    return tuple(dims)


def _union_rec(recs: list[_Rec], rank: int) -> _Rec:
    return _Rec(_merge_dims(recs, rank),
                frozenset().union(*[r.partial for r in recs])
                if recs else frozenset(),
                frozenset().union(*[r.ringed for r in recs])
                if recs else frozenset())


def _check_perm(perm, n: int | None) -> str | None:
    """None if ``perm`` is sound, else a one-line defect description.

    Duplicated destinations (nondeterministic overwrite), duplicated
    sources, and out-of-range indices are defects; a PARTIAL shift with
    unique pairs (halo exchange — edges fall off, receivers of nothing
    get zeros) is legal.
    """
    pairs = [tuple(int(x) for x in p) for p in perm]
    srcs = [s for s, _ in pairs]
    dsts = [d for _, d in pairs]
    if n is not None:
        bad = [p for p in pairs
               if not (0 <= p[0] < n and 0 <= p[1] < n)]
        if bad:
            return f"index out of range for axis size {n}: {bad}"
    if len(set(dsts)) != len(dsts):
        dup = sorted({d for d in dsts if dsts.count(d) > 1})
        return (f"duplicated destination(s) {dup} — nondeterministic "
                f"overwrite (two sends land on one device)")
    if len(set(srcs)) != len(srcs):
        dup = sorted({s for s in srcs if srcs.count(s) > 1})
        return (f"duplicated source(s) {dup} — a device sends twice while "
                f"another's data is dropped")
    return None


def _full_ring_defect(perm, n: int) -> str | None:
    """Ring-op contract: the perm must be a TRUE permutation of 0..n-1."""
    basic = _check_perm(perm, n)
    if basic is not None:
        return basic
    pairs = [tuple(int(x) for x in p) for p in perm]
    if (len(pairs) != n or {s for s, _ in pairs} != set(range(n))
            or {d for _, d in pairs} != set(range(n))):
        return (f"not a permutation of the full axis (size {n}): sources "
                f"{sorted({s for s, _ in pairs})}, destinations "
                f"{sorted({d for _, d in pairs})} — dropped sources read "
                f"garbage (zeros) on the ring")
    return None


# ---------------------------------------------------------------------------
# The dataflow interpreter over one shard_map body.
# ---------------------------------------------------------------------------

class _Interp:
    def __init__(self, axis_sizes: dict, report):
        self.axis_sizes = axis_sizes
        self.report = report     # report(check, key, detail)

    # -- record store ------------------------------------------------------
    def _read(self, env: dict, atom) -> _Rec:
        if not hasattr(atom, "aval") or isinstance(atom, jex_core.Literal):
            return _Rec.empty(_rank(atom))
        return env.get(id(atom), _Rec.empty(_rank(atom)))

    def run(self, jaxpr, in_recs: list[_Rec]) -> list[_Rec]:
        """Interpret ``jaxpr`` (an open Jaxpr); returns outvar records."""
        env: dict[int, _Rec] = {}
        for var in jaxpr.constvars:
            env[id(var)] = _Rec.empty(_rank(var))
        for var, rec in zip(jaxpr.invars, in_recs):
            env[id(var)] = rec
        for eqn in jaxpr.eqns:
            self._eqn(env, eqn)
        return [self._read(env, v) for v in jaxpr.outvars]

    # -- one equation ------------------------------------------------------
    def _eqn(self, env: dict, eqn) -> None:
        name = eqn.primitive.name
        ins = [self._read(env, v) for v in eqn.invars]
        out_rank = _rank(eqn.outvars[0]) if eqn.outvars else 0

        if name in _AXIS_COLLECTIVES:
            self._collective(env, eqn, ins)
            return
        if name == "dot_general":
            env[id(eqn.outvars[0])] = self._dot_general(eqn, ins)
            return
        if name in _REDUCE_PRIMS:
            axes = set(eqn.params.get("axes", ()))
            r = ins[0]
            partial = set(r.partial)
            if name in ("reduce_sum", "reduce_prod"):
                # summing a sharded dim locally creates a partial
                for d in axes:
                    if d < len(r.dims):
                        partial |= r.dims[d]
            dims = tuple(dv for d, dv in enumerate(r.dims)
                         if d not in axes)
            for ov in eqn.outvars:
                env[id(ov)] = _Rec(dims, frozenset(partial), r.ringed)
            return
        if name == "transpose":
            perm = eqn.params["permutation"]
            r = ins[0]
            dims = (tuple(r.dims[p] for p in perm)
                    if len(r.dims) == len(perm) else
                    (frozenset(),) * out_rank)
            env[id(eqn.outvars[0])] = _Rec(dims, r.partial, r.ringed)
            return
        if name == "broadcast_in_dim":
            r = ins[0]
            shape = eqn.params["shape"]
            bcast = eqn.params["broadcast_dimensions"]
            dims = [frozenset()] * len(shape)
            for i, d in enumerate(bcast):
                if i < len(r.dims):
                    dims[d] = r.dims[i]
            env[id(eqn.outvars[0])] = _Rec(tuple(dims), r.partial, r.ringed)
            return
        if name == "squeeze":
            r = ins[0]
            drop = set(eqn.params["dimensions"])
            dims = tuple(dv for d, dv in enumerate(r.dims) if d not in drop)
            env[id(eqn.outvars[0])] = _Rec(dims, r.partial, r.ringed)
            return
        if name == "concatenate":
            rec = _union_rec(ins, out_rank)
            env[id(eqn.outvars[0])] = rec
            return
        if name in _DIM_PRESERVING:
            r = ins[0] if ins else _Rec.empty(out_rank)
            rec = _Rec(r.dims if len(r.dims) == out_rank
                       else (frozenset(),) * out_rank,
                       frozenset().union(*[i.partial for i in ins])
                       if ins else frozenset(),
                       frozenset().union(*[i.ringed for i in ins])
                       if ins else frozenset())
            for ov in eqn.outvars:
                env[id(ov)] = rec
            return
        if name in _ELEMENTWISE or name in ("dynamic_update_slice",
                                            "dynamic_slice"):
            arr = [r for r, v in zip(ins, eqn.invars)
                   if _rank(v) == out_rank] or ins
            rec = _union_rec(arr, out_rank)
            for ov in eqn.outvars:
                env[id(ov)] = rec
            return
        if name == "scan":
            self._scan(env, eqn, ins)
            return
        if name == "while":
            self._while(env, eqn, ins)
            return
        if name == "cond":
            self._cond(env, eqn, ins)
            return
        if name in ("pjit", "closed_call", "core_call", "remat",
                    "checkpoint", "custom_jvp_call", "custom_vjp_call",
                    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr"):
            sub = self._one_sub(eqn)
            if sub is not None and len(sub.invars) == len(ins):
                outs = self.run(sub, ins)
                for ov, rec in zip(eqn.outvars, outs):
                    env[id(ov)] = rec
                return
        # opaque fallback (pallas_call, gather/scatter, rng, unknown):
        # dims tracking is lost, partial/ringed propagate conservatively.
        self._opaque(env, eqn, ins)

    def _opaque(self, env: dict, eqn, ins: list[_Rec]) -> None:
        partial = (frozenset().union(*[r.partial for r in ins])
                   if ins else frozenset())
        ringed = (frozenset().union(*[r.ringed for r in ins])
                  if ins else frozenset())
        for ov in eqn.outvars:
            env[id(ov)] = _Rec((frozenset(),) * _rank(ov), partial, ringed)
        # sub-jaxprs of unhandled higher-order prims may still bind
        # collectives: a ppermute there must credit `ringed`, a psum must
        # discharge — approximate both by scanning for collective names.
        for sub in _sub_jaxprs(eqn):
            red, rung = _collectives_in(sub)
            if rung or red:
                for ov in eqn.outvars:
                    r = env[id(ov)]
                    env[id(ov)] = _Rec(r.dims, r.partial - red,
                                       r.ringed | rung)

    # -- collectives -------------------------------------------------------
    def _collective(self, env: dict, eqn, ins: list[_Rec]) -> None:
        name = eqn.primitive.name
        axes = _axes_of(eqn.params)
        unknown = [a for a in axes if a not in self.axis_sizes]
        if unknown:
            self.report(
                "unknown-collective-axis", f"{name}:{unknown}",
                f"{name} bound over axis {unknown} but the enclosing "
                f"shard_map mesh carries only "
                f"{sorted(self.axis_sizes)} — it would resolve against "
                f"whatever axis is in scope, never what the rulebook "
                f"meant")
        if name == "ppermute":
            sizes = [self.axis_sizes.get(a) for a in axes]
            n = None
            if all(s is not None for s in sizes):
                n = int(np.prod(sizes)) if sizes else None
            defect = _check_perm(eqn.params.get("perm", ()), n)
            if defect:
                self.report("ppermute-not-permutation",
                            f"{axes}:{eqn.params.get('perm')}",
                            f"ppermute over {axes}: {defect}")
        for iv, ov in zip(eqn.invars, eqn.outvars):
            r = self._read(env, iv)
            partial, ringed = r.partial, r.ringed
            if name in _REDUCING:
                partial = partial - set(axes)
            if name == "ppermute":
                ringed = ringed | set(axes)
            dims = r.dims
            if name in ("psum_scatter", "reduce_scatter"):
                d = eqn.params.get("scatter_dimension", 0)
                if d < len(dims):
                    dims = tuple(dv | set(axes) if i == d else dv
                                 for i, dv in enumerate(dims))
            elif name == "all_gather":
                dims = tuple(dv - set(axes) for dv in dims)
            elif name == "all_to_all":
                # all_to_all retargets the sharded dim (split_axis →
                # concat_axis); modelling that reliably across jax
                # spellings isn't worth it — drop dim tracking, which
                # can only lose findings (quiet), never invent one.
                dims = (frozenset(),) * _rank(ov)
            if len(dims) != _rank(ov):
                dims = (frozenset(),) * _rank(ov)
            env[id(ov)] = _Rec(dims, partial, ringed)
        # n-ary collectives with a single output (psum of a tree zips;
        # leftover outvars — be safe)
        for ov in eqn.outvars[len(eqn.invars):]:
            env[id(ov)] = _union_rec(ins, _rank(ov))

    def _dot_general(self, eqn, ins: list[_Rec]) -> _Rec:
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs, rhs = ins[0], ins[1]
        contracted = frozenset()
        for d in lc:
            if d < len(lhs.dims):
                contracted |= lhs.dims[d]
        for d in rc:
            if d < len(rhs.dims):
                contracted |= rhs.dims[d]
        l_free = [d for d in range(len(lhs.dims))
                  if d not in lc and d not in lb]
        r_free = [d for d in range(len(rhs.dims))
                  if d not in rc and d not in rb]
        dims = ([lhs.dims[b] | (rhs.dims[b2] if b2 < len(rhs.dims)
                                else frozenset())
                 for b, b2 in zip(lb, rb)]
                + [lhs.dims[d] for d in l_free]
                + [rhs.dims[d] for d in r_free])
        return _Rec(tuple(dims),
                    lhs.partial | rhs.partial | contracted,
                    lhs.ringed | rhs.ringed)

    # -- higher-order ------------------------------------------------------
    def _one_sub(self, eqn):
        subs = list(_sub_jaxprs(eqn))
        return subs[0] if len(subs) == 1 else None

    def _scan(self, env: dict, eqn, ins: list[_Rec]) -> None:
        sub = self._one_sub(eqn)
        nc = eqn.params.get("num_consts", 0)
        nk = eqn.params.get("num_carry", 0)
        if sub is None or len(sub.invars) != len(ins):
            self._opaque(env, eqn, ins)
            return
        # xs operands are sliced along the leading axis inside the body
        body_in = list(ins[:nc + nk])
        for r in ins[nc + nk:]:
            body_in.append(_Rec(r.dims[1:], r.partial, r.ringed))
        # two rounds: a partial/ring arising mid-scan rides the carry back
        outs = self.run(sub, body_in)
        carry = [_union_rec([a, b], len(a.dims))
                 for a, b in zip(body_in[nc:nc + nk], outs[:nk])]
        outs = self.run(sub, body_in[:nc] + carry + body_in[nc + nk:])
        for ov, rec in zip(eqn.outvars[:nk], outs[:nk]):
            env[id(ov)] = rec
        for ov, rec in zip(eqn.outvars[nk:], outs[nk:]):
            env[id(ov)] = _Rec((frozenset(),) + rec.dims, rec.partial,
                               rec.ringed)

    def _while(self, env: dict, eqn, ins: list[_Rec]) -> None:
        body = eqn.params.get("body_jaxpr")
        body = getattr(body, "jaxpr", body)
        nb = eqn.params.get("body_nconsts", 0)
        nc = eqn.params.get("cond_nconsts", 0)
        carry = ins[nc + nb:]
        if body is None or len(body.invars) != nb + len(carry):
            self._opaque(env, eqn, ins)
            return
        consts = ins[nc:nc + nb]
        outs = self.run(body, consts + carry)
        carry2 = [_union_rec([a, b], len(a.dims))
                  for a, b in zip(carry, outs)]
        outs = self.run(body, consts + carry2)
        for ov, rec in zip(eqn.outvars, outs):
            env[id(ov)] = rec

    def _cond(self, env: dict, eqn, ins: list[_Rec]) -> None:
        branches = eqn.params.get("branches", ())
        ops = ins[1:]
        per_branch = []
        for br in branches:
            sub = getattr(br, "jaxpr", br)
            if len(sub.invars) != len(ops):
                self._opaque(env, eqn, ins)
                return
            per_branch.append(self.run(sub, ops))
        for i, ov in enumerate(eqn.outvars):
            recs = [b[i] for b in per_branch]
            env[id(ov)] = _union_rec(recs, _rank(ov))


def _collectives_in(jaxpr) -> tuple[frozenset, frozenset]:
    """(axes reduced over, axes ppermuted over) anywhere in a jaxpr."""
    red, rung = set(), set()
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _REDUCING:
            red.update(_axes_of(eqn.params))
        elif name == "ppermute":
            rung.update(_axes_of(eqn.params))
        for sub in _sub_jaxprs(eqn):
            r2, g2 = _collectives_in(sub)
            red.update(r2)
            rung.update(g2)
    return frozenset(red), frozenset(rung)


# ---------------------------------------------------------------------------
# Entry points.
# ---------------------------------------------------------------------------

def _iter_shard_maps(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _iter_shard_maps(sub)


def _spec_axes(spec) -> list[frozenset]:
    """Mesh axis names per dimension of one shard_map ``PartitionSpec``
    (an entry is None, a name, or a tuple of names)."""
    return [frozenset() if entry is None else frozenset(
        str(n) for n in (entry if isinstance(entry, (tuple, list))
                         else (entry,))) for entry in spec]


def lint_collectives(closed_jaxpr, *, config: str) -> list[Finding]:
    """All shard_map-body soundness checks over one traced step."""
    findings: list[Finding] = []
    seen: set[tuple[str, str]] = set()

    def report(check: str, key: str, detail: str):
        if (check, key) in seen:
            return
        seen.add((check, key))
        findings.append(Finding(config, "collective", check, "error",
                                detail))

    for eqn in _iter_shard_maps(closed_jaxpr.jaxpr):
        mesh = eqn.params.get("mesh")
        axis_sizes = dict(getattr(mesh, "shape", {}) or {})
        body = eqn.params.get("jaxpr")
        body = getattr(body, "jaxpr", body)
        if body is None or not axis_sizes:
            continue
        in_recs = []
        for var, spec in zip(body.invars, eqn.params["in_specs"]):
            rank = _rank(var)
            dims = (_spec_axes(spec) + [frozenset()] * rank)[:rank]
            in_recs.append(_Rec(tuple(dims), frozenset(), frozenset()))
        interp = _Interp(axis_sizes, report)
        outs = interp.run(body, in_recs)
        for i, (rec, spec) in enumerate(zip(outs, eqn.params["out_specs"])):
            out_axes = frozenset().union(*_spec_axes(spec))
            offending = rec.partial - rec.ringed - out_axes
            if offending:
                report(
                    "unreduced-partial-escape", f"out{i}:{sorted(offending)}",
                    f"shard_map output #{i} contracted dimension(s) "
                    f"sharded over {sorted(offending)} but escapes "
                    f"claiming replication over that axis, with no "
                    f"psum/psum_scatter (and no ring) on the way out — "
                    f"each shard returns its local partial sum")
    return findings


# ---------------------------------------------------------------------------
# Mirrored-ring soundness over the registered custom_vjp ring pairs.
# ---------------------------------------------------------------------------

def _perms_in(jaxpr) -> set:
    perms = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "ppermute":
            perms.add(tuple(sorted(tuple(int(x) for x in p)
                                   for p in eqn.params["perm"])))
        for sub in _sub_jaxprs(eqn):
            perms.update(_perms_in(sub))
    return perms


def _inverse(perm: tuple) -> tuple:
    return tuple(sorted((d, s) for s, d in perm))


def _trace_ringed(fn, axis: str, n: int, args) -> set:
    """Trace ``fn(axis, *args)`` under a size-``n`` shard_map (abstract,
    replicated per-shard args — trace only, never executed) and return
    the set of ppermute perms it binds."""
    mesh = Mesh(np.array(jax.devices()[:n]), (axis,))
    wrapped = jax.shard_map(functools.partial(fn, axis), mesh=mesh,
                            in_specs=P(), out_specs=P(), check_vma=False)
    closed = jax.make_jaxpr(lambda *a: wrapped(*a))(*args)
    return _perms_in(closed.jaxpr)


def ring_soundness(ops=None, *, axis_sizes=(2, 4),
                   config: str = "collective_matmul") -> list[Finding]:
    """The mirrored-ring fence over ``ring_inventory()`` (or an explicit
    op list, for tests): every perm either side binds must be a TRUE
    permutation of the full axis, and the backward's rings must each be
    the forward ring or its exact inverse. A backward that binds no ring
    while the forward does has fallen back to blocking collectives — the
    overlap the custom_vjp exists to preserve is silently gone."""
    if ops is None:
        from dtf_tpu.ops import collective_matmul as cm

        ops = cm.ring_inventory()
    findings: list[Finding] = []
    axis = "ring"
    usable = [n for n in axis_sizes if n <= len(jax.devices())]
    for op in ops:
        for n in usable:
            fwd = _trace_ringed(op.fwd, axis, n, op.fwd_args(n))
            bwd = _trace_ringed(op.bwd, axis, n, op.bwd_args(n))
            for side, perms in (("forward", fwd), ("backward", bwd)):
                for p in perms:
                    defect = _full_ring_defect(p, n)
                    if defect:
                        findings.append(Finding(
                            config, "collective", "ppermute-not-permutation",
                            "error",
                            f"{op.name} {side} ring at axis size {n}: "
                            f"{defect}"))
            legal = fwd | {_inverse(p) for p in fwd}
            rogue = [p for p in bwd if p not in legal]
            if rogue:
                findings.append(Finding(
                    config, "collective", "ring-not-mirrored", "error",
                    f"{op.name} backward at axis size {n} binds ring(s) "
                    f"{sorted(rogue)} that are neither the forward ring "
                    f"nor its inverse {sorted(legal)} — the mirrored-ring "
                    f"invariant (overlap surviving grad) is broken"))
            if fwd and not bwd:
                findings.append(Finding(
                    config, "collective", "ring-not-mirrored", "error",
                    f"{op.name} backward at axis size {n} binds NO ring "
                    f"while the forward does — grad fell back to blocking "
                    f"collectives; the custom_vjp mirror is gone"))
    return findings
