"""The persistent kernel-tune winner store (schema + lookup).

Two files, one precedence rule:

- ``KERNEL_TUNE.json`` at the repo root — the COMMITTED golden: winners
  measured on chip and banked, so a machine without the chip and the
  CPU sim resolve to real data instead of hard-coded literals. Regenerated by
  ``python -m dtf_tpu.tune seed`` (from the committed sweep artifacts);
  committed like the STATIC_ANALYSIS.json golden, with the change
  justified in the PR that measured it on the chip.
- ``KERNEL_TUNE.local.json`` next to ``.jax_cache/`` (repo root,
  gitignored) — winners measured on THIS machine; shadows the golden on
  identical (kind, key) and is where a sweep on this machine banks
  first.

Schema (``schema`` pins it; a reader that sees a different version
treats the file as stale and falls back — never half-parses):

.. code-block:: json

    {"schema": 1,
     "generated_by": "python -m dtf_tpu.tune seed",
     "entries": [
       {"kind": "flash_fwd",
        "key": {"seq": 8192, "heads": 8, "head_dim": 128,
                "dtype": "bfloat16", "causal": true, "window": 0,
                "n_devices": 1, "backend": "tpu"},
        "winner": {"block_q": 512, "block_k": 1024, "block_h": 1},
        "metric": {"flash_fwd_s": 1.0e-3},
        "source": "banked fwd block-sweep rows (KERNEL_TUNE_SWEEP.json)",
        "measured": true}]}

(The values illustrate the schema; no such entry is banked.)
Kinds: ``flash_fwd`` (forward block_q/block_k/block_h), ``flash_bwd``
(the independent backward blocks), ``fused_ce`` (Pallas CE
block_n/block_v), ``lm_loss`` (the loss-path choice, keyed by whether
the monolithic logits fit). ``measured: false`` marks a policy-seeded
entry (e.g. the token-chunk preference awaiting its on-chip A/B row) —
the resolver still uses it, but never warns about overriding it.

Lookup is NEAREST-SHAPE, not exact-match: hard fields (kind, causal,
windowed-or-not, fits) must match; soft numeric fields (seq, heads,
head_dim, vocab, ...) score by log2 distance and backend/dtype
mismatches add small penalties, so a CPU-sim query at the GPT train
shape (seq 1024, d_head 64) resolves to the banked v5e winner at
(8192, 128) — the committed on-chip data — rather than a literal.
Ties break deterministically (measured first, then canonical JSON).

Corrupt or stale files NEVER raise into a kernel call: they resolve as
empty with one warning, and the resolver falls back to the built-in
defaults (tested in tests/test_tune.py).

This module is jax-free on purpose (see the package docstring).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Optional

SCHEMA_VERSION = 1
GOLDEN_BASENAME = "KERNEL_TUNE.json"
LOCAL_BASENAME = "KERNEL_TUNE.local.json"

#: key fields that must match EXACTLY for an entry to be a candidate.
_HARD_FIELDS = ("causal", "fits")
#: STRING key fields that must match exactly when both sides carry them
#: (the ``spec_k`` kind keys winners by model/draft architecture labels —
#: a draft-k measured for one model pair must never resolve for another;
#: ``matmul_precision`` keys by projection site/parallel the same way —
#: an int8 winner for a column projection must never resolve for a row
#: one, whose ring quantizes the ACCUMULATOR, a different error model).
_HARD_STR_FIELDS = ("model", "draft", "site", "parallel")
#: numeric key fields scored by |log2(query/entry)| when both present.
_SOFT_FIELDS = ("seq", "heads", "head_dim", "vocab", "d_model",
                "n_devices", "batch", "n_slots", "d_in", "d_out")
_BACKEND_PENALTY = 0.5
_DTYPE_PENALTY = 0.25
#: window matters as a BUCKET (windowed vs full) — the grid-skip changes
#: the block-shape economics, the exact width much less so.
_WINDOW_PENALTY = 2.0


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def golden_path() -> str:
    return (os.environ.get("DTF_KERNEL_TUNE_GOLDEN")
            or os.path.join(repo_root(), GOLDEN_BASENAME))


def local_path() -> str:
    return (os.environ.get("DTF_KERNEL_TUNE_PATH")
            or os.path.join(repo_root(), LOCAL_BASENAME))


@dataclasses.dataclass(frozen=True)
class Entry:
    kind: str
    key: dict
    winner: dict
    metric: dict = dataclasses.field(default_factory=dict)
    source: str = ""
    measured: bool = False

    def canonical_key(self) -> str:
        """Stable identity for shadowing/merging: kind + sorted key."""
        return self.kind + "|" + json.dumps(self.key, sort_keys=True)

    def to_json(self) -> dict:
        return {"kind": self.kind, "key": self.key, "winner": self.winner,
                "metric": self.metric, "source": self.source,
                "measured": self.measured}

    @staticmethod
    def from_json(obj) -> Optional["Entry"]:
        try:
            if not (isinstance(obj.get("kind"), str)
                    and isinstance(obj.get("key"), dict)
                    and isinstance(obj.get("winner"), dict)):
                return None
            return Entry(kind=obj["kind"], key=dict(obj["key"]),
                         winner=dict(obj["winner"]),
                         metric=dict(obj.get("metric") or {}),
                         source=str(obj.get("source", "")),
                         measured=bool(obj.get("measured", False)))
        except (TypeError, AttributeError):
            return None


def _warn(msg: str) -> None:
    # absl may not be configured in every consumer (the telemetry CLI
    # idiom); stderr-degrading keeps the store usable anywhere.
    try:
        from absl import logging as absl_logging

        absl_logging.warning("%s", msg)
    except Exception:  # pragma: no cover - absl always present in-repo
        import sys

        print(f"WARNING: {msg}", file=sys.stderr)


def load_file(path: str) -> list[Entry]:
    """Entries from one cache file; corrupt/stale → [] + one warning."""
    if not os.path.exists(path):
        return []
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        _warn(f"kernel-tune cache {path} is unreadable ({e!r}); "
              "falling back to built-in defaults")
        return []
    if not isinstance(data, dict) or data.get("schema") != SCHEMA_VERSION:
        _warn(f"kernel-tune cache {path} has schema "
              f"{data.get('schema') if isinstance(data, dict) else '?'} "
              f"(want {SCHEMA_VERSION}); treating as stale")
        return []
    out = []
    for obj in data.get("entries", []):
        e = Entry.from_json(obj) if isinstance(obj, dict) else None
        if e is not None:
            out.append(e)
    return out


class TuneStore:
    """Local-shadows-golden union with nearest-shape lookup."""

    def __init__(self, entries: list[Entry]):
        self.entries = list(entries)

    @staticmethod
    def from_files(local: str, golden: str) -> "TuneStore":
        merged: dict[str, Entry] = {}
        for e in load_file(golden):
            merged[e.canonical_key()] = e
        for e in load_file(local):       # local wins on identical key
            merged[e.canonical_key()] = e
        return TuneStore(list(merged.values()))

    def lookup(self, kind: str, key: dict) -> Optional[Entry]:
        """The nearest banked winner for ``key`` (None = no candidate).

        ``key`` uses the same field names as stored entries; fields the
        query leaves out are not scored. ``backend=None`` means no
        preference (the flags-time loss resolution, which runs before
        jax is importable).
        """
        best: Optional[tuple] = None
        for e in self.entries:
            if e.kind != kind:
                continue
            d = _distance(key, e.key)
            if d is None:
                continue
            rank = (d, not e.measured, e.canonical_key())
            if best is None or rank < best[0]:
                best = (rank, e)
        return None if best is None else best[1]


def _distance(query: dict, entry_key: dict) -> Optional[float]:
    """Match score (lower = closer); None = hard-field mismatch."""
    for f in _HARD_FIELDS:
        if f in query and f in entry_key and \
                bool(query[f]) != bool(entry_key[f]):
            return None
    for f in _HARD_STR_FIELDS:
        if f in query and f in entry_key and \
                str(query[f]) != str(entry_key[f]):
            return None
    d = 0.0
    qw, ew = query.get("window", 0) or 0, entry_key.get("window", 0) or 0
    if bool(qw) != bool(ew):
        d += _WINDOW_PENALTY
    for f in _SOFT_FIELDS:
        a, b = query.get(f), entry_key.get(f)
        if a and b:
            try:
                d += abs(math.log2(float(a) / float(b)))
            except (ValueError, ZeroDivisionError):
                d += 1.0
    qb, eb = query.get("backend"), entry_key.get("backend")
    if qb is not None and eb is not None and qb != eb:
        d += _BACKEND_PENALTY
    qd, ed = query.get("dtype"), entry_key.get("dtype")
    if qd is not None and ed is not None and qd != ed:
        d += _DTYPE_PENALTY
    return d


_STORE_CACHE: dict[tuple, TuneStore] = {}
#: resolver-layer caches register a clear hook here so EVERY
#: invalidation path (including a cache-file write) drops their
#: memoized plans too — without this, a same-process bank-then-resolve
#: would keep serving pre-merge winners from the lru caches. The
#: listener must only clear its own state, never call back in.
_INVALIDATE_LISTENERS: list = []


def on_invalidate(fn) -> None:
    if fn not in _INVALIDATE_LISTENERS:
        _INVALIDATE_LISTENERS.append(fn)


def load_store() -> TuneStore:
    """The process-cached store (kernel calls resolve per-trace; the
    JSON must not be re-read per call). :func:`invalidate_cache` drops
    it — tests that plant caches via DTF_KERNEL_TUNE_* call that."""
    key = (local_path(), golden_path())
    store = _STORE_CACHE.get(key)
    if store is None:
        store = TuneStore.from_files(*key)
        _STORE_CACHE.clear()     # one live (local, golden) pair at a time
        _STORE_CACHE[key] = store
    return store


def invalidate_cache() -> None:
    _STORE_CACHE.clear()
    for fn in list(_INVALIDATE_LISTENERS):
        fn()


def merge_entries(path: str, new_entries: list[Entry], *,
                  generated_by: str = "") -> int:
    """Merge ``new_entries`` into the cache file at ``path`` (replacing
    same-(kind, key) entries, keeping everything else), sorted for
    stable diffs. Returns the total entry count written. A corrupt or
    stale existing file is OVERWRITTEN (its entries were unusable
    anyway — load_file already warned)."""
    merged: dict[str, Entry] = {e.canonical_key(): e
                                for e in load_file(path)}
    for e in new_entries:
        merged[e.canonical_key()] = e
    entries = sorted(merged.values(), key=Entry.canonical_key)
    payload = {"schema": SCHEMA_VERSION,
               "generated_by": generated_by or "dtf_tpu.tune",
               "entries": [e.to_json() for e in entries]}
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    invalidate_cache()
    return len(entries)
