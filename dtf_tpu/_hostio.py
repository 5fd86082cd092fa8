"""Host-plane file IO choke points — the atomic-write discipline's one
sanctioned constructor (the ``ring_perm`` idiom applied to file writes).

Every host-plane file that another process or thread READS while this one
writes it — publish manifests, heartbeats, a log directory's merged
report files, controller/postmortem jsonl — must be written through this
module. The host soundness pass (``dtf_tpu/analysis/host.py``) fences the
jax-free control plane for exactly that: a raw ``open(path, "w")`` or bare
``os.rename``/``os.replace`` anywhere else is a ``non-atomic-publish``
finding, because a reader racing a raw write sees a torn file (the class
of bug publish.py's manifest contract and the controller's torn-heartbeat
guard exist to prevent).

Two primitives, matching the two shapes host files take:

- :func:`atomic_replace` — whole-file replace via unique tmp +
  ``os.replace``: readers observe either the complete old bytes or the
  complete new bytes, never a prefix. The tmp name is pid-suffixed so
  concurrent writers (per-host heartbeats under one logdir) never tread
  on each other's staging file.
- :func:`append_line` — single-writer line append (jsonl). One short
  line per call: a sub-``PIPE_BUF`` append from the one owning process
  lands contiguously on POSIX, and readers tolerate a torn TAIL line by
  construction (``fault/controller.read_heartbeat``'s guard; a jsonl
  parser skips the last partial line). Multi-writer jsonl is NOT
  supported — each file has one owning process.

Stdlib-only on purpose: a parent whose children need the chip stays off
jax, and a package import pulls jax, so such a process can load this file
directly via ``importlib`` file-location instead of the package path.
"""

from __future__ import annotations

import os
from typing import Union


def atomic_replace(path: str, data: Union[str, bytes]) -> None:
    """Write ``data`` to ``path`` atomically (unique tmp + ``os.replace``).

    A reader opening ``path`` at any moment sees a complete file — the
    previous content or the new content, never a partial write. A crash
    mid-write leaves the target untouched (the stale tmp is garbage a
    later successful replace of the same path simply ignores).
    """
    path = os.fspath(path)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    mode = "wb" if isinstance(data, bytes) else "w"
    try:
        with open(tmp, mode) as f:
            f.write(data)
        os.replace(tmp, path)       # THE commit point — atomic
    except BaseException:
        # never leave the staging file behind on a failed commit: an
        # orphan tmp next to a manifest reads as a crashed publish
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def append_line(path: str, line: str) -> None:
    """Append one newline-terminated line to a single-writer jsonl file.

    ``line`` must not itself contain newlines (one record per line is the
    jsonl contract readers rely on to skip a torn tail).
    """
    path = os.fspath(path)
    if "\n" in line:
        raise ValueError("append_line takes ONE record (no embedded "
                         "newlines) — the jsonl torn-tail guard depends "
                         "on one-record-per-line")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "a") as f:
        f.write(line + "\n")


__all__ = ["atomic_replace", "append_line"]
