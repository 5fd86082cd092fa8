"""CLI: (re)seed the committed golden / inspect a resolution.

    python -m dtf_tpu.tune seed            # artifacts -> KERNEL_TUNE.json
    python -m dtf_tpu.tune seed --local    # -> KERNEL_TUNE.local.json
    python -m dtf_tpu.tune show --seq=1024 --heads=12 --head-dim=64

One JSON line on stdout (the bench.py idiom); exit 0 unless the
arguments are unusable. No jax anywhere — this must run on a machine
with no backend at all.
"""

from __future__ import annotations

import json
import sys

from dtf_tpu.tune import cache, resolver, search


def _arg(argv, name, default=None):
    pre = f"--{name}="
    for a in argv:
        if a.startswith(pre):
            return a[len(pre):]
    return default


def main(argv: list[str]) -> int:
    if not argv or argv[0] not in ("seed", "show"):
        print(json.dumps({"error": "usage: python -m dtf_tpu.tune "
                          "seed [--local] | show [--seq=..] [--heads=..] "
                          "[--head-dim=..] [--dtype=..] [--backend=..]"}))
        return 2
    if argv[0] == "seed":
        root = _arg(argv, "root") or cache.repo_root()
        entries = search.seed_entries(root)
        path = (cache.local_path() if "--local" in argv
                else cache.golden_path())
        total = cache.merge_entries(path, entries,
                                    generated_by="python -m dtf_tpu.tune "
                                    "seed")
        print(json.dumps({
            "seeded": len(entries), "total_entries": total, "path": path,
            "kinds": sorted({e.kind for e in entries}),
            "winners": {e.canonical_key(): e.winner for e in entries}},
            sort_keys=True))
        return 0
    # show: resolve one flash shape + the fused-CE/loss-path buckets
    seq = int(_arg(argv, "seq", "1024"))
    heads = int(_arg(argv, "heads", "12"))
    head_dim = int(_arg(argv, "head-dim", "64"))
    dtype = _arg(argv, "dtype", "bfloat16")
    backend = _arg(argv, "backend")
    plan = resolver.flash_plan(seq=seq, heads=heads, head_dim=head_dim,
                               dtype=dtype, causal=True, window=0,
                               backend=backend)
    ce = resolver.fused_ce_plan(vocab=int(_arg(argv, "vocab", "50304")),
                                d_model=heads * head_dim, dtype=dtype,
                                backend=backend)
    out = {"flash": plan.__dict__, "fused_ce": ce.__dict__,
           "golden": cache.golden_path(), "local": cache.local_path()}
    for fits in (True, False):
        w = resolver.lm_loss_winner(
            fits=fits, vocab=int(_arg(argv, "vocab", "50304")), seq=seq,
            batch=int(_arg(argv, "batch", "8")), backend=backend)
        out[f"lm_loss_fits_{fits}"] = None if w is None else w.__dict__
    print(json.dumps(out, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
