"""Bounded-history JSON artifact plumbing shared by the bench parents.

bench_telemetry.py / bench_profile.py merge rows into committed
``{"runs": [...]}`` artifacts (TELEMETRY.json, DEVICE_PROFILE.json) and
fence new rows against the newest committed same-config baseline. Their
parents must NEVER import anything under dtf_tpu (importing the package
pulls jax, and a parent whose children need the chip stays off jax — the
_dtf_watchdog contract), so the shared helpers live here at the repo
root, importable with no dependencies at all.
"""

from __future__ import annotations

import importlib.util
import json
import os


def _hostio():
    """Load ``dtf_tpu/_hostio.py`` by file location — executing ONLY that
    stdlib-only module, never ``dtf_tpu/__init__`` (which pulls jax).
    One atomic-replace
    implementation for the whole repo, without breaking the parents'
    never-import-dtf_tpu contract."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "dtf_tpu", "_hostio.py")
    spec = importlib.util.spec_from_file_location("_dtf_hostio", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_runs(path: str) -> list:
    """The artifact's runs list; [] for a missing/malformed file (the
    artifact reader must not be able to fail the bench reporting on it)."""
    try:
        with open(path) as f:
            prev = json.load(f)
        if isinstance(prev, dict) and isinstance(prev.get("runs"), list):
            return prev["runs"]
    except (OSError, ValueError):
        pass
    return []


def merge_runs(path: str, entry: dict, meta: dict,
               keep_runs: int = 20) -> dict:
    """Append one row (newest LAST, history bounded) and rewrite the
    artifact — telemetry.run.merge_artifact's semantics, jax-free."""
    data = {"runs": load_runs(path)}
    data["runs"] = (data["runs"] + [{**entry, **meta}])[-keep_runs:]
    # atomic replace via the repo's one choke point: concurrent report
    # readers race these merges
    _hostio().atomic_replace(path, json.dumps(data, indent=1))
    return data


def same_config(a: dict, b: dict, keys) -> bool:
    """Rows are fence-comparable only when every identity key matches —
    rows measured under different shapes/models/backends never are."""
    return all(a.get(k) == b.get(k) for k in keys)
