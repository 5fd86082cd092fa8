"""Source-attributed comms provenance — who introduced each collective.

XLA op metadata survives lowering into the optimized HLO — a
``stack_frame_id`` into the module's own FileNames / FileLocations /
StackFrames tables (older XLA: inline ``source_file``/``source_line``) —
so every collective the comms-budget fence counts can be attributed to
the Python line that introduced it. That turns a
``collective-count-drift`` finding from "all-reduce 126→127" into
"all-reduce +1 at dtf_tpu/core/train.py:396", and gives PR review a
per-line delta view (``python -m dtf_tpu.analysis --diff``).

Provenance is recorded in the golden next to each budget but is NOT
itself fenced: line numbers shift on every unrelated edit to a traced
file, and a fence over them would page on comment changes. It exists to
*attribute* count/byte drift the opcode fence already caught, and to
print review diffs — staleness only ever makes an annotation slightly
off, never a finding wrong. (``--write-golden`` refreshes it wholesale;
expect provenance churn in the JSON diff whenever traced sources moved.)

Paths are normalized repo-relative (anchored on the last ``dtf_tpu`` /
``tests`` / ``scripts`` path segment; anything outside the repo — jax,
flax internals — keeps its basename) so goldens compare across machines.
"""

from __future__ import annotations

import re
from typing import Any, Mapping

#: repo path anchors: everything from the LAST occurrence of one of these
#: segments on is the stable cross-machine identity of a source file.
_ANCHORS = ("dtf_tpu", "tests", "scripts")

_META_RE = re.compile(
    r'source_file="(?P<file>[^"]+)"\s+source_line=(?P<line>\d+)')
_FRAME_RE = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW_RE = re.compile(r"^(\d+) (.*)$")

#: instruction name on the LHS of an HLO line: `%all-reduce.2 = ...` —
#: the SAME name the profiler stamps into XPlane op events as ``hlo_op``,
#: which is what makes device time joinable to source lines.
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=")


def _rel(path: str) -> str:
    parts = path.replace("\\", "/").split("/")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in _ANCHORS:
            return "/".join(parts[i:])
    return parts[-1]


def frame_sites(hlo_text: str) -> dict:
    """``{stack_frame_id: "file:line"}`` from the tables at the head of
    the module text: a frame names a file location, a location names a
    file and a line. The frame an op carries is the innermost user frame
    (jax and flax exclude their own), the site the inline metadata of
    older XLA named."""
    tables: dict[str, dict[int, str]] = {}
    current = None
    for line in hlo_text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            current = tables.setdefault(line, {})
        elif current is not None:
            row = _TABLE_ROW_RE.match(line)
            if row:
                current[int(row.group(1))] = row.group(2)
            elif line.strip():
                break          # the first computation: tables are over
    files = {i: v.strip('"') for i, v in tables.get("FileNames", {}).items()}
    locs = {}
    for i, v in tables.get("FileLocations", {}).items():
        f = re.search(r"file_name_id=(\d+)", v)
        ln = re.search(r"\bline=(\d+)", v)
        if f and ln and int(f.group(1)) in files:
            locs[i] = f"{_rel(files[int(f.group(1))])}:{ln.group(1)}"
    sites = {}
    for i, v in tables.get("StackFrames", {}).items():
        loc = re.search(r"file_location_id=(\d+)", v)
        if loc and int(loc.group(1)) in locs:
            sites[i] = locs[int(loc.group(1))]
    return sites


def _site(line: str, frames: Mapping[int, str]) -> str:
    meta = _META_RE.search(line)
    if meta:
        return f"{_rel(meta.group('file'))}:{meta.group('line')}"
    frame = _FRAME_RE.search(line)
    if frame:
        return frames.get(int(frame.group(1)), "<unattributed>")
    return "<unattributed>"


def instruction_sites(hlo_text: str, *, ops=None) -> dict:
    """``{instruction_name: {"op": opcode, "loc": "file:line"}}`` for every
    collective instruction in optimized HLO text.

    The shared source-anchoring helper: the comms-budget golden records
    per-``file:line`` aggregates (:func:`collective_provenance`), while the
    XPlane device-profile parser (:mod:`dtf_tpu.telemetry.profile`) needs
    the PER-INSTRUCTION map — a profiled ``all-reduce.2`` event joins to
    its Python call site through the instruction name, so device seconds
    can be attributed to the line that issued the collective. ``ops``
    restricts the opcode set (default: the fence's COLLECTIVE_OPS).
    Instructions without source metadata map to ``"<unattributed>"``.
    """
    from dtf_tpu.analysis import hlo as hlo_pass

    frames = frame_sites(hlo_text)
    sites: dict[str, dict] = {}
    for line in hlo_pass.strip_comments(hlo_text).splitlines():
        m = hlo_pass._COLLECTIVE_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        if ops is not None and op not in ops:
            continue
        nm = _INSTR_RE.match(line)
        if nm is None:
            continue
        nbytes, _ = hlo_pass._shape_bytes(m.group("type"))
        sites[nm.group("name")] = {"op": op, "loc": _site(line, frames),
                                   "bytes": nbytes}
    return sites


def collective_provenance(hlo_text: str) -> dict:
    """``{op: {"file:line": {count, bytes}}}`` from optimized HLO text.

    Reuses the hlo pass's opcode matcher line-by-line (HLO prints one op
    per line) and pairs each collective with the ``metadata={...}`` on
    its own line; collectives with no source metadata (rare: fusion
    roots synthesized by passes) land under ``"<unattributed>"``.
    """
    from dtf_tpu.analysis import hlo as hlo_pass

    frames = frame_sites(hlo_text)
    prov: dict[str, dict[str, dict]] = {}
    for line in hlo_pass.strip_comments(hlo_text).splitlines():
        m = hlo_pass._COLLECTIVE_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        nbytes, _ = hlo_pass._shape_bytes(m.group("type"))
        slot = prov.setdefault(op, {}).setdefault(
            _site(line, frames), {"count": 0, "bytes": 0})
        slot["count"] += 1
        slot["bytes"] += nbytes
    return prov


def profile_site_map(hlo_texts) -> dict:
    """Flatten ``instruction_sites`` over several programs' HLO texts into
    one ``{hlo_op_name: {"op", "loc", "bytes"}}`` join table for the
    device-profile parser. ``hlo_texts``: iterable of optimized HLO
    strings (or a single string). Later programs win name collisions —
    instruction names are unique within a module, and profiled runs window
    one program at a time, so collisions only matter across programs that
    never share a trace."""
    if isinstance(hlo_texts, str):
        hlo_texts = (hlo_texts,)
    out: dict[str, dict] = {}
    for text in hlo_texts:
        out.update(instruction_sites(text))
    return out


def provenance_delta(got: Mapping[str, Any] | None,
                     want: Mapping[str, Any] | None) -> list[str]:
    """Human-readable per-line delta, most-moved first; [] when clean."""
    got, want = got or {}, want or {}
    rows = []
    for op in sorted(set(got) | set(want)):
        g_op, w_op = got.get(op, {}), want.get(op, {})
        for loc in sorted(set(g_op) | set(w_op)):
            g = g_op.get(loc, {"count": 0, "bytes": 0})
            w = w_op.get(loc, {"count": 0, "bytes": 0})
            dc, db = g["count"] - w["count"], g["bytes"] - w["bytes"]
            if dc or db:
                rows.append((abs(dc), abs(db),
                             f"{op} {dc:+d} ({db:+,} B) at {loc} "
                             f"[{w['count']}→{g['count']}]"))
    rows.sort(reverse=True)
    return [r[2] for r in rows]


def attribute_drift(op: str, got_prov: Mapping[str, Any] | None,
                    want_prov: Mapping[str, Any] | None,
                    *, limit: int = 3) -> str:
    """Short suffix for a drift finding: the top moved lines of ``op``.

    Empty string when EITHER side carries no provenance at all (a
    pre-provenance golden, a metadata-stripped backend): diffing real
    call sites against an empty record would list every existing line as
    "drift" and misdirect the reader — better no attribution than wrong
    attribution. An op merely absent on one side (0 → N call sites) is
    attributed normally.
    """
    if got_prov is None or want_prov is None:
        return ""
    got = got_prov.get(op)
    want = want_prov.get(op)
    if got is None and want is None:
        return ""
    lines = provenance_delta({op: got or {}}, {op: want or {}})
    if not lines:
        return ""
    shown = "; ".join(lines[:limit])
    more = f" (+{len(lines) - limit} more lines)" if len(lines) > limit \
        else ""
    return f" — {shown}{more}"
