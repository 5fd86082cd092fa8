"""Trace-level lints over the train step's jaxpr (no compile, no device).

``jax.make_jaxpr`` on the (jitted) step with abstract inputs costs one
trace — seconds even for the flagship — and exposes failure classes the
type system doesn't:

- ``float64-leak``      — a wide dtype in the step (a stray numpy f64
  scalar upcasting a whole tree; only bites when x64 is enabled, which is
  exactly when nobody is looking at dtypes).
- ``host-callback``     — ``pure_callback``/``io_callback``/``debug``
  callbacks inside the compiled step: a device→host sync per step, the
  kind of "why is MFU 12%?" regression that static analysis catches for
  free.
- ``collective-outside-shard-map`` — ``psum``/``all_gather``/axis-index
  primitives bound outside any ``shard_map`` scope (e.g. under a stray
  ``vmap(axis_name=...)``): they compile, but against whatever axis
  happens to be in scope — never what the mesh intended.

The walker recurses through every higher-order primitive (pjit, scan,
while, cond, custom_vjp, remat) — including ``shard_map`` bodies, where
the f64/callback lints still apply — and tracks whether the current
sub-jaxpr is inside a ``shard_map``, which only suppresses the
axis-collective lint (collectives there are the whole point).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
from jax.extend import core as jex_core

from dtf_tpu.analysis.findings import Finding

#: primitives legal only inside shard_map (axis-env consumers).
AXIS_PRIMS = frozenset({
    "psum", "psum_invariant", "pmean", "pmax", "pmin", "ppermute",
    "pbroadcast", "pvary", "pgather", "all_gather", "all_gather_invariant",
    "all_to_all", "psum_scatter", "reduce_scatter", "axis_index",
})

#: primitive-name fragments that mean "host round-trip inside the step".
CALLBACK_FRAGMENTS = ("callback", "outside_call", "infeed", "outfeed")

#: dtypes that should never appear in a TPU train step.
WIDE_DTYPES = ("float64", "complex128")

#: primitives whose sub-jaxprs run under a bound mesh-axis scope: the walk
#: DOES descend (f64/callback lints apply inside), but marks the subtree
#: as inside shard_map so the axis-collective lint stays quiet there.
_SHARD_MAP_PRIMS = frozenset({"shard_map"})


def _sub_jaxprs(eqn):
    """Yield every closed/open jaxpr hiding in an eqn's params."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jex_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jex_core.Jaxpr):
                yield v


def _walk(jaxpr, visit: Callable, *, inside_shard_map: bool) -> None:
    for eqn in jaxpr.eqns:
        visit(eqn, inside_shard_map)
        name = eqn.primitive.name
        inner = inside_shard_map or name in _SHARD_MAP_PRIMS
        for sub in _sub_jaxprs(eqn):
            _walk(sub, visit, inside_shard_map=inner)


def lint_jaxpr(closed_jaxpr, *, config: str) -> list[Finding]:
    """All trace-level lints over one closed jaxpr."""
    findings: list[Finding] = []
    seen: set[tuple[str, str]] = set()   # (check, key) de-dup

    def add(check: str, key: str, detail: str):
        if (check, key) in seen:
            return
        seen.add((check, key))
        findings.append(Finding(config, "jaxpr", check, "error", detail))

    def visit(eqn, inside_shard_map: bool):
        name = eqn.primitive.name
        if any(frag in name for frag in CALLBACK_FRAGMENTS):
            add("host-callback", name,
                f"host callback primitive {name!r} inside the step "
                f"(device->host sync every step)")
        if name in AXIS_PRIMS and not inside_shard_map:
            axes = eqn.params.get("axes",
                                  eqn.params.get("axis_name", "?"))
            add("collective-outside-shard-map", f"{name}:{axes}",
                f"{name} over {axes!r} bound outside any shard_map")
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dtype = str(getattr(aval, "dtype", ""))
            if dtype in WIDE_DTYPES:
                add("float64-leak", f"{name}:{dtype}",
                    f"{name} produces {dtype} "
                    f"{getattr(aval, 'shape', ())} inside the step")

    _walk(closed_jaxpr.jaxpr, visit, inside_shard_map=False)
    return findings


def trace_step(step_fn: Callable, *abstract_args: Any):
    """``make_jaxpr`` helper: trace the (possibly jitted) step on
    ShapeDtypeStructs only — no device buffers, no compile."""
    return jax.make_jaxpr(step_fn)(*abstract_args)
