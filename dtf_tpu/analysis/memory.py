"""Static HBM accounting — the "memory" pass and the fit planner.

The comms fence (analysis/hlo.py) proves every AOT program's collective
mix sound, but until this pass only ONE memory number was pinned
(``temp_size_in_bytes``).  The dominant failure mode left for chip time
was the silent one: a config that OOMs a 16 GiB v5e, or a donated train
state whose aliasing XLA quietly dropped (the PR 1 BN-stats-freeze
class).  This pass closes both holes on the CPU sim:

- **breakdown fence** (:func:`check_memory`): the full per-program HBM
  breakdown from AOT ``memory_analysis()`` — argument/output/temp/
  generated-code/alias bytes, recorded per budget in
  ``STATIC_ANALYSIS.json`` and fenced fail-closed per FIELD with the
  same ``--diff``/``--write-golden`` idiom as the comms budgets.
- **resident-state model** (:func:`resident_bytes` /
  :func:`state_accounting`): an analytic per-device pricing of every
  program argument — params + optimizer moments + KV/page pools —
  built from the registry's DECLARED shardings (the same introspection
  hooks the launchers use: ``train.abstract_train_state``,
  ``sharding.zero1_opt_specs``, ``serve.pages.pool_abstract``) and
  cross-checked against the compiled executable's argument bytes and
  per-leaf committed shardings.  A leaf that silently changed dtype or
  replication (a dropped ``in_shardings`` entry, a spec change XLA
  answers with replication) is a ``state-accounting-drift`` finding
  naming the leaf, not an 8x-bigger argument buffer discovered on chip.
- **donation soundness** (:func:`donation_soundness`): for every
  program lowered with donated arguments, each donated-and-kept leaf
  must be aliased to an output in the executable
  (``input_output_alias`` in the optimized HLO header) — a donation
  XLA dropped is a ``dropped-donation`` finding.  This turns the BN
  freeze from a bisected runtime mystery into a CPU-sim lint.
- **fit planner** (:func:`fit`): inverts the resident model under a
  per-chip HBM budget — max KV slots and page-pool size for serve
  configs (bf16 AND int8 KV, real-scale ``eval_shape`` pricing, no
  compile), max global batch for train configs (analytic resident +
  a measured affine temp-vs-batch model from two tiny AOT compiles).
  ``python -m dtf_tpu.analysis fit --config=gpt_serve --hbm-gb=16``.

Everything here runs on the 8-device CPU sim; nothing needs a chip.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Mapping, Optional, Sequence

import numpy as np

from dtf_tpu.analysis.findings import Finding

PyTree = Any

#: memory_analysis() fields recorded in every budget and fenced per field.
MEMORY_FIELDS = (
    ("temp_bytes", "temp_size_in_bytes"),
    ("arg_bytes", "argument_size_in_bytes"),
    ("out_bytes", "output_size_in_bytes"),
    ("alias_bytes", "alias_size_in_bytes"),
    ("gen_code_bytes", "generated_code_size_in_bytes"),
)

#: aggregate state-accounting tolerance: XLA pads/alignments and scalar
#: bookkeeping the analytic model doesn't price.  Anything beyond this is
#: a leaf-level dtype/replication change, which is exactly the finding.
ACCOUNTING_REL_TOL = 0.02
ACCOUNTING_ABS_TOL = 4096


def fmt_bytes(n: int) -> str:
    """453K / 1.2M style — the per-field drift findings' spelling."""
    n = int(n)
    for unit, div in (("G", 1 << 30), ("M", 1 << 20), ("K", 1 << 10)):
        if abs(n) >= div:
            v = n / div
            return f"{v:.1f}{unit}" if abs(v) < 10 else f"{v:.0f}{unit}"
    return str(n)


# ---------------------------------------------------------------------------
# Per-device pricing arithmetic (deliberately NOT jax's shard_shape — the
# model must be an independent accounting the compiled side can contradict).
# ---------------------------------------------------------------------------

def _spec_device_bytes(shape: Sequence[int], dtype, spec,
                       mesh_shape: Mapping[str, int]) -> int:
    """THE pricing arithmetic: per-device bytes of one array under a
    PartitionSpec — each sharded dim ceil-divided by the product of its
    mesh axes (XLA pads ragged shards up; axes missing from the mesh
    count as size 1), unsharded dims at full extent.  Shared by the
    fence-side :func:`leaf_device_bytes` and the fit planner's
    :func:`_price_spec_tree` so the two cannot drift apart."""
    dims = [int(d) for d in shape]
    for i, entry in enumerate(spec):
        if entry is None or i >= len(dims):
            continue
        names = (entry,) if isinstance(entry, str) else tuple(entry)
        k = 1
        for n in names:
            k *= int(mesh_shape.get(n, 1))
        dims[i] = -(-dims[i] // k)
    n = 1
    for d in dims:
        n *= d
    return n * np.dtype(dtype).itemsize


def leaf_device_bytes(shape: Sequence[int], dtype, sharding=None) -> int:
    """Per-device bytes of one array leaf under a NamedSharding
    (replicated leaves — ``sharding=None`` — cost their full extent on
    every device)."""
    if sharding is None or getattr(sharding, "spec", None) is None:
        return _spec_device_bytes(shape, dtype, (), {})
    return _spec_device_bytes(shape, dtype, sharding.spec,
                              dict(sharding.mesh.shape))


def tree_device_bytes(tree: PyTree, shardings: PyTree = None) -> int:
    """Summed per-device bytes of a ShapeDtypeStruct tree.

    ``shardings``: an optional matching tree of NamedShardings (or ONE
    NamedSharding broadcast over every leaf — jit's prefix-spec
    convention); without it each leaf's own ``.sharding`` is used, and a
    leaf with neither is priced replicated (its full extent).
    """
    import jax

    leaves = jax.tree.leaves(tree)
    shs = _broadcast_shardings(shardings, len(leaves), tree)
    total = 0
    for leaf, sh in zip(leaves, shs):
        if sh is None:
            sh = getattr(leaf, "sharding", None)
        total += leaf_device_bytes(leaf.shape, leaf.dtype, sh)
    return total


def _broadcast_shardings(shardings, n_leaves: int, tree) -> list:
    """Resolve a shardings argument to one entry per leaf of ``tree``."""
    import jax

    if shardings is None:
        return [None] * n_leaves
    if not isinstance(shardings, (list, tuple, dict)) and not hasattr(
            shardings, "tree_flatten"):
        # a bare sharding object: jit broadcasts it over the subtree
        if hasattr(shardings, "spec"):
            return [shardings] * n_leaves
    flat = jax.tree.leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec") or x is None)
    if len(flat) != n_leaves:
        raise ValueError(
            f"shardings tree has {len(flat)} leaves for a {n_leaves}-leaf "
            f"value tree")
    return flat


def _flat_declared(view) -> tuple[list, list, list]:
    """``(paths, leaves, shardings)`` for the program args
    ``(state, batch)``.

    Declared shardings come from ``view.arg_shardings`` (the in_shardings
    the builder passed to jit) when present, else from each abstract
    leaf's own ``.sharding`` (the serve views embed them), else None —
    the caller prices such leaves at the executable's committed sharding
    (no independent claim to check).
    """
    import jax

    flat = jax.tree_util.tree_flatten_with_path((view.state, view.batch))[0]
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path) for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    decl: list = [getattr(leaf, "sharding", None) for leaf in leaves]
    arg_sh = getattr(view, "arg_shardings", None)
    if arg_sh is not None:
        n_state = len(jax.tree.leaves(view.state))
        state_sh = _broadcast_shardings(arg_sh[0], n_state, view.state)
        batch_sh = _broadcast_shardings(arg_sh[1], len(leaves) - n_state,
                                        view.batch)
        decl = state_sh + batch_sh
    return paths, leaves, decl


# ---------------------------------------------------------------------------
# (a) the breakdown fence
# ---------------------------------------------------------------------------

def memory_breakdown(compiled) -> Optional[dict]:
    """The fenced ``memory_analysis()`` fields of one compiled program,
    or None on a backend without an allocator report (the golden check
    then fails closed — see :func:`check_memory`)."""
    try:
        mem = compiled.memory_analysis()
        return {name: int(getattr(mem, attr)) for name, attr in MEMORY_FIELDS}
    except Exception:  # noqa: BLE001 — backends without an allocator report
        return None


def hbm_peak_bytes(mem: Mapping[str, int]) -> int:
    """The planner's peak-resident estimate for one program: arguments +
    outputs + peak temps + generated code, minus donated (aliased) output
    bytes that reuse argument buffers."""
    return (mem.get("arg_bytes", 0) + mem.get("out_bytes", 0)
            + mem.get("temp_bytes", 0) + mem.get("gen_code_bytes", 0)
            - mem.get("alias_bytes", 0))


def memory_delta(got: Mapping[str, int] | None,
                 want: Mapping[str, int] | None) -> list[str]:
    """Per-field human-readable delta lines (``--diff``); [] when clean."""
    got, want = got or {}, want or {}
    lines = []
    for field in sorted(set(got) | set(want)):
        g, w = got.get(field), want.get(field)
        if g != w:
            lines.append(
                f"memory {field} {fmt_bytes(w) if w is not None else '?'}"
                f"→{fmt_bytes(g) if g is not None else '?'} "
                f"[{w}→{g}]")
    return lines


def check_memory(got: Mapping[str, int] | None,
                 want: Mapping[str, int] | None, *,
                 config: str) -> list[Finding]:
    """Exact per-field fence against the golden's memory breakdown.

    Fails CLOSED: a golden that pins memory numbers while the backend
    reports none means the fence did not run — that is a finding, not a
    skip (otherwise a later ``--write-golden`` would silently drop the
    memory entries and nobody would notice the fence died).
    """
    if want is None:
        return []
    if got is None:
        return [Finding(
            config, "memory", "memory-unavailable", "error",
            "golden pins a memory breakdown but memory_analysis() "
            "reported nothing on this backend — the HBM fence did not "
            "run")]
    findings = []
    for field in sorted(set(want) | set(got)):
        g, w = got.get(field), want.get(field)
        if g != w:
            findings.append(Finding(
                config, "memory", "memory-bytes-drift", "error",
                f"{field} {fmt_bytes(w or 0)}→{fmt_bytes(g or 0)} "
                f"({(g or 0) - (w or 0):+,} B vs golden; accumulators / "
                f"stashes / argument layouts moved — regenerate with "
                f"--write-golden if intended)"))
    return findings


# ---------------------------------------------------------------------------
# (b) resident-state model + cross-check
# ---------------------------------------------------------------------------

def resident_bytes(view) -> dict:
    """Analytic per-device pricing of one program's arguments.

    ``{"state_bytes", "batch_bytes", "total_bytes"}`` — the declared
    cost of everything resident across calls (state: params, moments,
    KV pools) plus the per-call batch, each leaf priced at its DECLARED
    sharding via :func:`leaf_device_bytes`.
    """
    import jax

    _, leaves, decl = _flat_declared(view)
    n_state = len(jax.tree.leaves(view.state))
    state = sum(leaf_device_bytes(lf.shape, lf.dtype, sh)
                for lf, sh in zip(leaves[:n_state], decl[:n_state]))
    batch = sum(leaf_device_bytes(lf.shape, lf.dtype, sh)
                for lf, sh in zip(leaves[n_state:], decl[n_state:]))
    return {"state_bytes": state, "batch_bytes": batch,
            "total_bytes": state + batch}


def _committed_flat(compiled) -> Optional[list]:
    """Flat per-arg committed shardings from the executable (None entries
    = the leaf was pruned out of the compiled program), or None when the
    surface is unavailable on this jax."""
    import jax

    try:
        args_sh = compiled.input_shardings[0]
    except Exception:  # noqa: BLE001 — older stages without the property
        return None
    return jax.tree.leaves(
        args_sh, is_leaf=lambda x: x is None or hasattr(x, "spec"))


def state_accounting(config_name: str, view, compiled, *,
                     rel_tol: float = ACCOUNTING_REL_TOL,
                     abs_tol: int = ACCOUNTING_ABS_TOL) -> list[Finding]:
    """Cross-check the analytic model against the compiled executable.

    Two layers:

    - per-leaf: every KEPT argument's committed sharding must price to
      the same per-device bytes as its declared sharding — a leaf the
      partitioner answered with replication (or whose declared dtype no
      longer matches what the builder constructs) is named directly.
    - aggregate: the summed model (kept leaves only — jit prunes unused
      args, e.g. the eval program drops ``opt_state``) must match
      ``memory_analysis().argument_size_in_bytes`` within tolerance.
    """
    findings: list[Finding] = []
    mem = memory_breakdown(compiled)
    committed = _committed_flat(compiled)
    paths, leaves, decl = _flat_declared(view)
    if committed is not None and len(committed) != len(leaves):
        return [Finding(
            config_name, "memory", "state-accounting-drift", "error",
            f"executable reports {len(committed)} argument leaves, the "
            f"declared state+batch has {len(leaves)} — the program and "
            f"the introspected state desynchronized")]

    model_kept = 0
    for i, leaf in enumerate(leaves):
        comm = committed[i] if committed is not None else None
        if committed is not None and comm is None:
            continue  # pruned: costs nothing in the executable
        d_sh = decl[i] if decl[i] is not None else comm
        d_bytes = leaf_device_bytes(leaf.shape, leaf.dtype, d_sh)
        model_kept += d_bytes
        if comm is not None and decl[i] is not None:
            c_bytes = leaf_device_bytes(leaf.shape, leaf.dtype, comm)
            if c_bytes != d_bytes:
                findings.append(Finding(
                    config_name, "memory", "state-accounting-drift",
                    "error",
                    f"{paths[i]}: declared {d_bytes:,} B/device "
                    f"(spec {getattr(d_sh, 'spec', None)}) but the "
                    f"executable committed {c_bytes:,} B/device "
                    f"(spec {getattr(comm, 'spec', None)}) — the leaf "
                    f"silently changed replication"))
    if mem is not None:
        got = mem["arg_bytes"]
        tol = max(abs_tol, int(rel_tol * max(model_kept, got)))
        if abs(got - model_kept) > tol:
            findings.append(Finding(
                config_name, "memory", "state-accounting-drift", "error",
                f"analytic resident model prices the kept arguments at "
                f"{model_kept:,} B/device but the executable allocates "
                f"{got:,} B/device (|Δ| > {tol:,} B) — a leaf silently "
                f"changed dtype or replication"))
    return findings


# ---------------------------------------------------------------------------
# (c) donation soundness
# ---------------------------------------------------------------------------

#: the module header's alias map: ``input_output_alias={ {0}: (2, {},
#: may-alias), ... }`` — each entry names the PARAMETER NUMBER an output
#: tuple index aliases.
_ALIAS_PARAM_RE = re.compile(r"\(\s*(\d+)\s*,\s*\{")


def aliased_param_numbers(hlo_text: str) -> set[int]:
    """Parameter numbers aliased to outputs in an optimized module."""
    head = hlo_text.split("\n", 1)[0]
    start = head.find("input_output_alias={")
    if start < 0:
        return set()
    # the attribute's map nests one {} per entry — cut at the matching
    # top-level close brace before scanning for `(N, {` param numbers.
    depth = 0
    end = len(head)
    for i in range(start + len("input_output_alias="), len(head)):
        if head[i] == "{":
            depth += 1
        elif head[i] == "}":
            depth -= 1
            if depth == 0:
                end = i
                break
    return {int(m) for m in _ALIAS_PARAM_RE.findall(head[start:end])}


def donated_flags(lowered) -> list[bool]:
    """Flat per-argument donation flags from ``lowered.args_info``."""
    import jax

    try:
        info = lowered.args_info
    except Exception:  # noqa: BLE001 — stages without args_info
        return []
    return [bool(getattr(a, "donated", False))
            for a in jax.tree.leaves(info)]


def donation_soundness(config_name: str, lowered, compiled,
                       *, arg_paths: Sequence[str] | None = None
                       ) -> list[Finding]:
    """Every donated-and-kept argument must be aliased to an output.

    A donated buffer XLA could not alias is deleted at dispatch while
    its contents go nowhere — exactly the class behind the warm-cache
    BN-stats freeze (donated executable deserialized without its
    aliasing).  Donated leaves jit PRUNED from the program are skipped:
    they never reach the runtime.
    """
    donated = donated_flags(lowered)
    if not any(donated):
        return []
    committed = _committed_flat(compiled)
    aliased = aliased_param_numbers(compiled.as_text())
    findings = []
    param = 0
    for i, d in enumerate(donated):
        kept = committed is None or committed[i] is not None
        if not kept:
            continue
        if d and param not in aliased:
            where = (arg_paths[i] if arg_paths and i < len(arg_paths)
                     else f"arg[{i}]")
            findings.append(Finding(
                config_name, "memory", "dropped-donation", "error",
                f"{where}: donated to the compiled program but aliased "
                f"to NO output (input_output_alias) — its buffer dies at "
                f"dispatch and the update silently vanishes (the "
                f"BN-stats-freeze class); drop the donation or alias the "
                f"leaf through"))
        param += 1
    return findings


def lint_program(config, view, lowered, compiled,
                 golden_budget: Mapping[str, Any] | None,
                 budget: Mapping[str, Any] | None = None) -> list[Finding]:
    """The whole memory pass for one registry program."""
    got_mem = (budget or {}).get("memory") if budget is not None \
        else memory_breakdown(compiled)
    want_mem = (golden_budget or {}).get("memory")
    paths, _, _ = _flat_declared(view)
    findings = check_memory(got_mem, want_mem, config=config.name)
    findings += state_accounting(config.name, view, compiled)
    findings += donation_soundness(config.name, lowered, compiled,
                                   arg_paths=paths)
    return findings


# ---------------------------------------------------------------------------
# temp-vs-scale affine model (the fit planner's)
# ---------------------------------------------------------------------------

def affine_temp_model(points: Mapping[int, int]) -> tuple[float, float]:
    """Least-squares ``temp(n) = intercept + slope * n`` over measured
    ``{n: temp_bytes}`` points (two suffice: scan stashes grow linearly
    in the scanned count — microbatches, batch rows)."""
    if len(points) < 2:
        raise ValueError("need at least two (n, temp_bytes) points")
    xs = np.array(sorted(points), dtype=np.float64)
    ys = np.array([points[int(x)] for x in xs], dtype=np.float64)
    slope, intercept = np.polyfit(xs, ys, 1)
    return float(intercept), float(slope)


def predict_temp(model: tuple[float, float], n: int) -> int:
    intercept, slope = model
    return int(round(intercept + slope * n))


# ---------------------------------------------------------------------------
# (d) the fit planner
# ---------------------------------------------------------------------------

def _price_spec_tree(tree: PyTree, specs: PyTree, mesh) -> int:
    """Per-device bytes of an abstract tree under a PartitionSpec tree
    (axes missing from ``mesh`` count as size 1) — the same arithmetic
    as the fence side, via :func:`_spec_device_bytes`."""
    import jax
    from jax.sharding import PartitionSpec as P

    mesh_shape = dict(mesh.shape)
    total = 0

    def one(spec, leaf):
        nonlocal total
        total += _spec_device_bytes(leaf.shape, leaf.dtype, spec,
                                    mesh_shape)
        return spec

    jax.tree.map(one, specs, tree, is_leaf=lambda x: isinstance(x, P))
    return total


#: the low-precision storage widths ``fit --precision`` prices, straight
#: from the hlo.py bit-width table (s8 / f8e4m3fn are both 8 bits).
_PRECISION_BITS = {"int8": 8, "fp8": 8}


def _quant_params_bytes(tree: PyTree, specs: PyTree, mesh,
                        precision: str) -> int:
    """Per-device bytes of a param tree with every matrix leaf (ndim>=2)
    stored at ``precision`` width plus its per-channel f32 scale sideband
    (one scale per output channel — the ops/quant.py layout: quantize
    over the contraction axis 0, scale shape (1,) + shape[1:]). Vector
    leaves (biases, layernorm gains) stay at their own dtype — they are
    noise next to the matrices and the quant tier never touches them."""
    import jax
    from jax.sharding import PartitionSpec as P

    bits = _PRECISION_BITS[precision]
    mesh_shape = dict(mesh.shape)
    total = 0

    def one(spec, leaf):
        nonlocal total
        if len(leaf.shape) >= 2:
            total += _spec_device_bytes(leaf.shape, np.dtype(np.int8),
                                        spec, mesh_shape) * bits // 8
            total += _spec_device_bytes((1,) + tuple(leaf.shape[1:]),
                                        np.dtype(np.float32), spec,
                                        mesh_shape)
        else:
            total += _spec_device_bytes(leaf.shape, leaf.dtype, spec,
                                        mesh_shape)
        return spec

    jax.tree.map(one, specs, tree, is_leaf=lambda x: isinstance(x, P))
    return total


def _fit_serve(config, hbm_bytes: int, *, max_len: int, kv_page_size: int,
               slots: Optional[int],
               precision: Optional[str] = None) -> dict:
    """Real-scale serve planning: params + per-slot KV + page pool,
    priced via ``eval_shape`` only (no compile).  Reports bf16 AND int8
    KV side by side — the two serving memory levers the engine ships."""
    from dtf_tpu.core import sharding as shd
    from dtf_tpu.serve import pages as pages_lib
    from dtf_tpu.serve.engine import engine_state_struct

    mesh = config.mesh()
    data_size = int(mesh.shape.get("data", 1))
    spec_view = config.spec_view(mesh)
    param_specs = shd.tree_specs(spec_view.params, spec_view.rules)
    params_dev = _price_spec_tree(spec_view.params, param_specs, mesh)

    base_cfg = config.fit_serve_cfg()
    out: dict = {
        "params_bytes_per_device": params_dev,
        "max_len": max_len, "kv_page_size": kv_page_size, "kv": {},
    }
    avail = hbm_bytes - params_dev
    avail_q = None
    if precision is not None:
        # --precision: weights held at 8-bit (matrix leaves + per-channel
        # scale sideband, the ops/quant.py layout) — the HBM the
        # quantized tier frees buys extra slots on the same chip.
        qparams_dev = _quant_params_bytes(spec_view.params, param_specs,
                                          mesh, precision)
        out["precision"] = precision
        out["params_bytes_per_device_at_precision"] = qparams_dev
        avail_q = hbm_bytes - qparams_dev

    # speculative decoding (fit_draft_cfg): the draft model is RESIDENT
    # state too — its params (priced under the same TP rules) and one
    # draft KV slot per target slot. "max slots with spec on" is then
    # answerable before any chip time: the slot budget shrinks by the
    # draft's per-slot cache and the draft params come off the top.
    draft_cfg = (config.fit_draft_cfg()
                 if config.fit_draft_cfg is not None else None)
    draft_params_dev = 0
    if draft_cfg is not None:
        import jax

        from dtf_tpu.models import gpt as gpt_lib

        dmodel = gpt_lib.GPT(draft_cfg, mesh)
        dparams = jax.eval_shape(lambda: dmodel.init(
            jax.random.PRNGKey(0),
            jax.numpy.zeros((1, 1), jax.numpy.int32)))["params"]
        dspecs = shd.tree_specs(dparams, gpt_lib.tp_rules)
        draft_params_dev = _price_spec_tree(dparams, dspecs, mesh)
        out["draft_params_bytes_per_device"] = draft_params_dev

    for kv_name in ("bf16", "int8"):
        kv_dtype = "" if kv_name == "bf16" else "int8"
        cfg = dataclasses.replace(base_cfg, kv_cache_dtype=kv_dtype)
        # price data_size slots (one per data shard) so the per-device
        # number is exactly one GLOBAL slot's cost — pricing a single
        # slot would overstate by the data-axis factor (ceil(1/N) = 1).
        struct = engine_state_struct(cfg, n_slots=data_size,
                                     max_len=max_len, mesh=mesh)
        per_slot = tree_device_bytes(struct) / data_size
        pool = pages_lib.pool_abstract(struct["cache"], 1, kv_page_size,
                                       mesh)
        per_page = tree_device_bytes(pool)
        max_slots = int(avail // per_slot) if avail > 0 else 0
        max_slots -= max_slots % data_size  # even slot sharding
        row = {
            "kv_bytes_per_slot_per_device": int(round(per_slot)),
            "page_bytes_per_device": per_page,
            "max_slots": max_slots,
        }
        if avail_q is not None:
            q_slots = int(avail_q // per_slot) if avail_q > 0 else 0
            q_slots -= q_slots % data_size
            row["max_slots_at_precision"] = q_slots
        if slots is not None:
            left = avail - slots * per_slot
            row["slots"] = slots
            row["max_pages_at_slots"] = max(0, int(left // per_page))
        if draft_cfg is not None:
            dstruct = engine_state_struct(
                dataclasses.replace(draft_cfg, kv_cache_dtype=kv_dtype),
                n_slots=data_size, max_len=max_len, mesh=mesh)
            per_slot_draft = tree_device_bytes(dstruct) / data_size
            savail = avail - draft_params_dev
            max_spec = (int(savail // (per_slot + per_slot_draft))
                        if savail > 0 else 0)
            max_spec -= max_spec % data_size
            row["draft_kv_bytes_per_slot_per_device"] = int(
                round(per_slot_draft))
            row["max_slots_with_spec"] = max_spec
            if avail_q is not None:
                # the quantized-DRAFT deployment (serve_gpt
                # --draft_precision): target weights stay bf16, the
                # draft's matrices go 8-bit.
                qdraft_dev = _quant_params_bytes(dparams, dspecs, mesh,
                                                 precision)
                sq = avail - qdraft_dev
                mq = (int(sq // (per_slot + per_slot_draft))
                      if sq > 0 else 0)
                mq -= mq % data_size
                row["max_slots_with_spec_at_draft_precision"] = mq
        out["kv"][kv_name] = row
    return out


def _scale_batch(batch: PyTree, b: int) -> PyTree:
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((b,) + tuple(x.shape[1:]), x.dtype),
        batch)


def _fit_train(config, hbm_bytes: int, *, opt: Optional[str],
               grad_accum: int, grad_shard: bool,
               act_scale: Optional[float], mesh=None,
               precision: Optional[str] = None) -> dict:
    """Train planning: analytic resident state + a measured affine
    temp-vs-batch model (two AOT compiles of the registry's own tiny
    program).  The batch inversion answers at PROGRAM scale — the same
    program the fence pins; ``act_scale`` (≈ (L·T·d)_real/(L·T·d)_tiny
    for the LM configs) extrapolates the activation slope to the
    real-scale model and prices the resident side from the real-scale
    spec view instead.  ``mesh`` overrides the config's own mesh — the
    elastic shrink pricing (``fit --hosts --lost``) reuses this whole
    path on the survivor mesh, no new compile machinery."""
    import jax
    from dtf_tpu.analysis import configs as cfgs
    from dtf_tpu.core import sharding as shd

    mesh = config.mesh() if mesh is None else mesh
    data_size = int(mesh.shape.get("data", 1))
    opt_name = opt or config.opt_name
    tx = cfgs.OPTIMIZER_FAMILIES[opt_name]()

    def resident_of(params, rules) -> dict:
        param_specs = shd.tree_specs(params, rules)
        p = _price_spec_tree(params, param_specs, mesh)
        opt_state = jax.eval_shape(tx.init, params)
        opt_specs = shd.zero1_opt_specs(tx, params, param_specs, mesh)
        o = _price_spec_tree(opt_state, opt_specs, mesh)
        acc = 0
        if grad_accum > 1:
            acc_specs = (shd.zero1_param_shard_specs(params, param_specs,
                                                     mesh)
                         if grad_shard else param_specs)
            f32 = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, np.float32), params)
            acc = _price_spec_tree(f32, acc_specs, mesh)
        return {"params_bytes": p, "opt_state_bytes": o,
                "accumulator_bytes": acc, "total_bytes": p + o + acc}

    view = config.step_view(mesh)
    b0 = jax.tree.leaves(view.batch)[0].shape[0]
    temps = {}
    for b in (b0, 2 * b0):
        # the memory pass probes the SAME registered program at a
        # scaled batch — a throwaway measurement lowering, not a
        # aot-ok: new program birth
        compiled = view.step.lower(view.state,
                                   _scale_batch(view.batch, b)).compile()
        temps[b] = int(compiled.memory_analysis().temp_size_in_bytes)
    intercept, slope = affine_temp_model(temps)
    _, leaves, decl = _flat_declared(view)
    n_batch = len(jax.tree.leaves(view.batch))
    batch_row = sum(
        leaf_device_bytes(lf.shape, lf.dtype, sh)
        for lf, sh in zip(leaves[-n_batch:], decl[-n_batch:])) / b0

    scale = 1.0 if act_scale is None else float(act_scale)
    if act_scale is None:
        # program scale: price the view's own declared state — the same
        # program the fence pins, no cross-scale claims.
        resident = {"total_bytes": resident_bytes(view)["state_bytes"]}
        label = "program"
    else:
        spec_view = config.spec_view(mesh)
        resident = resident_of(spec_view.params, spec_view.rules)
        label = "extrapolated"
    avail = hbm_bytes - resident["total_bytes"] - intercept * scale
    per_row = slope * scale + batch_row * scale
    max_batch = int(avail // per_row) if per_row > 0 and avail > 0 else 0
    grain = data_size * max(grad_accum, 1)
    max_batch -= max_batch % grain
    # fit verdict at the program's OWN global batch — the elastic shrink
    # question ("does the survivor mesh still carry the same global
    # batch?") is this number on the shrunk mesh vs the budget.
    need_at_b0 = int(resident["total_bytes"] + intercept * scale
                     + per_row * b0)
    out = {
        "scale": label, "opt": opt_name,
        "grad_accum": grad_accum, "grad_shard": grad_shard,
        "mesh": dict(mesh.shape),
        "resident_bytes_per_device": resident,
        "temp_model": {"intercept_bytes": int(intercept),
                       "bytes_per_batch_row": int(round(per_row)),
                       "measured": {str(k): v for k, v in temps.items()}},
        "act_scale": scale,
        "global_batch": b0,
        "hbm_needed_bytes_at_batch": need_at_b0,
        "fits_at_batch": bool(need_at_b0 <= hbm_bytes),
        "max_global_batch": max(0, max_batch),
    }
    if precision is not None:
        # --precision on a train config: the RESIDENT side is unchanged
        # by design (bf16/f32 master weights, full-precision grads — the
        # quant tier quantizes compute and ring bytes, not state), so
        # only the activation-temp slope shrinks: scaled by 8 bits over
        # the program's own activation width. A documented ESTIMATE —
        # the 8-bit activations live inside fusions XLA shapes as it
        # pleases — bounded below by the measured bf16 row it sits next
        # to (docs/ANALYSIS.md §fit).
        import jax as _jax

        act_bits = 8 * _jax.tree.leaves(view.state.params)[0].dtype.itemsize
        q_ratio = _PRECISION_BITS[precision] / act_bits
        q_per_row = slope * scale * q_ratio + batch_row * scale
        q_max = (int(avail // q_per_row)
                 if q_per_row > 0 and avail > 0 else 0)
        q_max -= q_max % grain
        out["precision"] = precision
        out["temp_model"]["bytes_per_batch_row_at_precision"] = int(
            round(q_per_row))
        out["max_global_batch_at_precision"] = max(0, q_max)
    return out


def fit(name: str, *, hbm_gb: float, max_len: int = 1024,
        kv_page_size: int = 64, slots: Optional[int] = None,
        opt: Optional[str] = None, grad_accum: int = 1,
        grad_shard: bool = False,
        act_scale: Optional[float] = None,
        hosts: Optional[int] = None, lost: int = 0,
        precision: Optional[str] = None,
        log_sink: bool = False) -> dict:
    """The fit planner: what fits a ``hbm_gb``-HBM chip under config
    ``name``'s mesh and sharding rules.  Serve configs answer max KV
    slots (bf16 AND int8) + page-pool size from a pure ``eval_shape``
    pricing at REAL model scale; train configs answer max global batch
    from analytic resident state + a measured temp model.

    ``hosts``/``lost`` (train configs): price the elastic shrink BEFORE
    the controller pays a relaunch — the config's mesh is split across
    ``hosts`` hosts, ``lost`` of them die, and the survivor mesh (data
    axis scaled down, everything else intact — ``fault/elastic.py``) is
    priced side by side with the full mesh at the SAME global batch.
    ``survivor.fits_at_batch`` is the controller's go/no-go: resident
    state grows (ZeRO-1 shards are 1/data') and temp grows (bigger
    per-device batch), so a shrink that no longer fits should relaunch
    at a smaller batch or fail loudly, not OOM on the chip.
    """
    from dtf_tpu.analysis import configs as cfgs

    config = cfgs.BY_NAME[name]
    if precision is not None and precision not in _PRECISION_BITS:
        raise ValueError(
            f"precision={precision!r} must be one of "
            f"{sorted(_PRECISION_BITS)} (bf16 is the default pricing)")
    hbm_bytes = int(hbm_gb * (1 << 30))
    out = {"mode": "fit", "config": name, "hbm_gb": hbm_gb,
           "mesh": dict(config.mesh().shape)}
    if hosts is not None:
        if config.fit_serve_cfg is not None:
            raise ValueError(
                "--hosts/--lost prices train meshes; a serve fleet "
                "shrinks by replica count, not mesh surgery")
        import jax

        from dtf_tpu.core.mesh import MeshConfig, make_mesh
        from dtf_tpu.fault.elastic import survivor_mesh_shape

        surv_shape = survivor_mesh_shape(out["mesh"], hosts, lost)
        n_surv = int(np.prod(list(surv_shape.values())))
        if n_surv > len(jax.devices()):
            raise ValueError(
                f"survivor mesh needs {n_surv} devices; the sim has "
                f"{len(jax.devices())}")
        surv_mesh = make_mesh(MeshConfig(**surv_shape),
                              devices=jax.devices()[:n_surv])
        kw = dict(opt=opt, grad_accum=grad_accum, grad_shard=grad_shard,
                  act_scale=act_scale, precision=precision)
        out.update({
            "kind": "train_shrink", "hosts": hosts, "lost": lost,
            "survivor_mesh": surv_shape,
            "full": _fit_train(config, hbm_bytes, **kw),
            "survivor": _fit_train(config, hbm_bytes, mesh=surv_mesh,
                                   **kw),
        })
        out["survivor_fits_same_batch"] = out["survivor"]["fits_at_batch"]
        return out
    if log_sink and config.fit_serve_cfg is None:
        raise ValueError(
            "--log-sink prices the SERVE request log sink (serve_gpt "
            "--log_sink_dir); pick a serve config")
    if config.fit_serve_cfg is not None:
        out["kind"] = "serve"
        out.update(_fit_serve(config, hbm_bytes, max_len=max_len,
                              kv_page_size=kv_page_size, slots=slots,
                              precision=precision))
        if log_sink:
            # the ISSUE 19 sink is scheduler-side file IO over token ids
            # the host already holds (the _retire record) — no device
            # transfer, no resident tensor, no extra program. An explicit
            # zero beats an absent row: capacity planning can CITE it.
            out["log_sink"] = {"hbm_delta_bytes": 0,
                               "host_side_only": True}
    else:
        out["kind"] = "train"
        out.update(_fit_train(config, hbm_bytes, opt=opt,
                              grad_accum=grad_accum, grad_shard=grad_shard,
                              act_scale=act_scale, precision=precision))
    return out
