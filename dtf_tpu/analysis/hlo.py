"""Comms budget from AOT-compiled HLO — the regression fence for
XLA-inserted collectives.

The train step is lowered and compiled on the 8-device CPU sim
(``step.lower(abstract_state, abstract_batch).compile()``); the optimized
HLO text then names every collective GSPMD inserted — the all-reduce of
the gradient mean, the reduce-scatter/all-gather pair of ZeRO-1, TP's
activation all-reduces, the pipeline's collective-permutes.  That mix IS
the framework's communication contract: an accidental resharding (a spec
change that makes XLA all-gather a weight every step) shows up here as a
count/byte diff against the committed golden (``STATIC_ANALYSIS.json``)
long before a chip ever runs it.

Parsing is textual on purpose: opcode spellings (``all-reduce``,
``all-gather``, ``reduce-scatter``, ``collective-permute``,
``all-to-all``, plus their async ``-start`` forms) are stable across XLA
versions, and byte sizes fall out of the result shapes.
"""

from __future__ import annotations

import json
import re
from typing import Any, Mapping

from dtf_tpu.analysis.findings import Finding

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")

#: bits per element — BITS, not bytes, so the packed sub-byte dtypes
#: (s4/u4) and the fp8 family count instead of silently contributing 0 B
#: to the fence (an int8-KV or fp8 collective that the byte fence cannot
#: see is a fence with a hole in it).
_DTYPE_BITS = {
    "pred": 8, "s2": 2, "u2": 2, "s4": 4, "u4": 4, "s8": 8, "u8": 8,
    "s16": 16, "u16": 16, "f16": 16, "bf16": 16,
    "s32": 32, "u32": 32, "f32": 32, "s64": 64, "u64": 64, "f64": 64,
    "c64": 64, "c128": 128,
    "f8e4m3": 8, "f8e4m3fn": 8, "f8e4m3b11fnuz": 8, "f8e4m3fnuz": 8,
    "f8e5m2": 8, "f8e5m2fnuz": 8, "f8e3m4": 8, "f8e8m0fnu": 8,
    "f4e2m1fn": 4,
}

#: HLO types that genuinely carry no payload in a collective result.
_TOKEN_DTYPES = frozenset({"token", "opaque"})

#: `lhs = <type> <opcode>(...)`; async `-start` counted, `-done` skipped
#: (same transfer), fused/computation names can't match: the opcode slot
#: sits right after the result type.
_COLLECTIVE_RE = re.compile(
    r"=\s+(?P<type>\([^=]*?\)|\S+)\s+"
    r"(?P<op>" + "|".join(re.escape(o) for o in COLLECTIVE_OPS) + r")"
    r"(?P<async>-start)?\(")

#: XLA numbers the elements of a long tuple type in comments
#: (``/*index=5*/``); their ``=`` would end the type slot of the matcher
#: above, and a combined all-reduce of more than five operands — the
#: whole gradient reduction, since the all-reduce combiner — went
#: uncounted. Matchers run on text with the comments taken out.
_COMMENT_RE = re.compile(r"/\*.*?\*/")


def strip_comments(hlo_text: str) -> str:
    return _COMMENT_RE.sub("", hlo_text)


#: dtype tokens are alphanumeric runs (f8e4m3fn, s4, bf16 — not just
#: letters+digits: the fp8 family interleaves them).
_SHAPE_RE = re.compile(r"(?P<dtype>[a-z][a-z0-9]*)\[(?P<dims>[0-9,]*)\]")


def _shape_bytes(type_str: str) -> tuple[int, set[str]]:
    """(total bytes, unknown dtypes) of every array shape in an HLO
    result type string.

    An unrecognized non-token dtype is NOT silently skipped: it would
    count 0 bytes and quietly hole the byte fence, so it is surfaced to
    the caller and becomes an ``unknown-dtype`` finding in
    :func:`check_budget`.
    """
    total = 0
    unknown: set[str] = set()
    for m in _SHAPE_RE.finditer(type_str):
        dtype = m.group("dtype")
        bits = _DTYPE_BITS.get(dtype)
        if bits is None:
            if dtype not in _TOKEN_DTYPES:
                unknown.add(dtype)
            continue
        n = 1
        for d in m.group("dims").split(","):
            if d:
                n *= int(d)
        total += (n * bits + 7) // 8
    return total, unknown


def collective_stats(hlo_text: str) -> dict:
    """Per-opcode ``{count, bytes}`` plus totals, from optimized HLO text.

    ``bytes`` is the per-device result payload of each collective (the
    resharding volume a step moves over the interconnect, up to reduction
    fan-in), summed over call sites. Collective results whose dtype the
    byte table does not know are listed under ``unknown_dtypes`` (present
    only when non-empty) — :func:`check_budget` turns that into a
    fail-closed finding rather than counting them as 0 bytes.
    """
    stats = {op: {"count": 0, "bytes": 0} for op in COLLECTIVE_OPS}
    unknown: set[str] = set()
    for m in _COLLECTIVE_RE.finditer(strip_comments(hlo_text)):
        op = m.group("op")
        nbytes, unk = _shape_bytes(m.group("type"))
        stats[op]["count"] += 1
        stats[op]["bytes"] += nbytes
        unknown |= unk
    stats["total"] = {
        "count": sum(stats[op]["count"] for op in COLLECTIVE_OPS),
        "bytes": sum(stats[op]["bytes"] for op in COLLECTIVE_OPS),
    }
    if unknown:
        stats["unknown_dtypes"] = sorted(unknown)
    return stats


def comms_budget(compiled) -> dict:
    """Budget dict for one compiled step (``lowered.compile()`` result).

    Besides the per-opcode collective stats, records the program's full
    HBM breakdown from ``memory_analysis()`` — argument/output/peak-temp/
    alias/generated-code bytes (``analysis/memory.MEMORY_FIELDS``), where
    grad-accum accumulators, activation stashes, collective staging
    buffers AND the resident state itself live.  The memory pass
    (:func:`dtf_tpu.analysis.memory.check_memory`) fences every field
    against the golden, so an accumulator-HBM regression (e.g. a
    ``--grad_shard`` config silently falling back to the replicated f32
    accumulator) or a state leaf going replicated fails tier-1 just like
    an extra all-gather does.
    """
    from dtf_tpu.analysis import memory as memory_pass

    text = compiled.as_text()
    budget = collective_stats(text)
    mem = memory_pass.memory_breakdown(compiled)
    if mem is not None:
        budget["memory"] = mem
    # source attribution per collective call site (analysis/provenance.py)
    # — recorded in the golden but never fenced on its own: it names the
    # offending line when the opcode fence above trips, and feeds --diff.
    from dtf_tpu.analysis import provenance

    budget["provenance"] = provenance.collective_provenance(text)
    return budget


def check_budget(budget: Mapping[str, Any], golden: Mapping[str, Any],
                 *, config: str) -> list[Finding]:
    """Exact count fence + byte fence against the committed golden.

    Counts must match exactly — one extra all-gather is precisely the
    regression this pass exists to catch.  Bytes must match exactly too
    (shapes are deterministic for a pinned jax/XLA); regenerate the golden
    via ``python -m dtf_tpu.analysis --write-golden`` when a change is
    intentional, and justify the diff in the PR.

    The budget's ``memory`` breakdown is fenced by the memory pass
    (:func:`dtf_tpu.analysis.memory.check_memory`), not here — this
    fence owns the collectives only.
    """
    from dtf_tpu.analysis import provenance

    findings = []
    got_prov = budget.get("provenance")
    want_prov = golden.get("provenance")
    if budget.get("unknown_dtypes"):
        # fail CLOSED: a collective whose dtype the byte table can't size
        # was counted as 0 B — the byte fence has a hole until the table
        # learns the dtype (_DTYPE_BITS).
        findings.append(Finding(
            config, "hlo", "unknown-dtype", "error",
            f"collective result dtype(s) {budget['unknown_dtypes']} not in "
            f"the byte table — counted as 0 B; teach _DTYPE_BITS the "
            f"dtype so the byte fence covers it"))
    for op in COLLECTIVE_OPS + ("total",):
        got = budget.get(op, {"count": 0, "bytes": 0})
        want = golden.get(op, {"count": 0, "bytes": 0})
        # total-row drift repeats the per-op rows; per-line attribution
        # only makes sense per opcode
        where = ("" if op == "total" else
                 provenance.attribute_drift(op, got_prov, want_prov))
        if got["count"] != want["count"]:
            findings.append(Finding(
                config, "hlo", "collective-count-drift", "error",
                f"{op}: {got['count']} in compiled step vs {want['count']} "
                f"in golden (regenerate with --write-golden if intended)"
                f"{where}"))
        elif got["bytes"] != want["bytes"]:
            findings.append(Finding(
                config, "hlo", "collective-bytes-drift", "error",
                f"{op}: {got['bytes']:,} B vs {want['bytes']:,} B golden "
                f"(count unchanged — shapes/dtypes moved){where}"))
    return findings


def load_golden(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def save_golden(path: str, budgets: Mapping[str, Any], *, meta: dict) -> None:
    doc = {"_meta": meta, "budgets": dict(sorted(budgets.items()))}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
