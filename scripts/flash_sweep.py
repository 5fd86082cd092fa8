#!/usr/bin/env python3
"""Time the three flash-attention kernels alone, on the chip, over blocks.

    python scripts/flash_sweep.py --shape 128,1024,64 --causal
    python scripts/flash_sweep.py --shape 384,512,64 --heads 12 --kv_mask
    python scripts/flash_sweep.py --shape 16,8192,128 --causal

One table a shape: for ``dtf_flash_fwd``, ``dtf_flash_dq`` and
``dtf_flash_dkv`` each, every candidate (query block, key block, tile_q,
tile_k) that :func:`flash_attention.vmem_bytes` lets under the scoped-VMEM
limit, with its milliseconds a call, its share of the kernel's roofline
(the forward's 2 matmuls, dq's 3 and dkv's 4 of the pairs the mask leaves,
over the chip's bf16 peak: each kernel against what IT must do, where the
benchmark's ``flash_bwd_roofline`` credits the backward 5 in all), and the
mechanism's counter from :func:`flash_attention.flash_tiles`: tiles
computed ÷ tiles in the square, tiles masked ÷ computed. The row marked
``rule`` is what :func:`flash_attention.flash_blocks` gives the shape;
``--block_h 2`` adds the opt-in head-folded forward at its blocks.

This is the record ``flash_blocks``'s constants are read from (PERF.md §6,
PR 35), not a benchmark: it takes no seed, checks nothing against a
reference and writes no ledger line. A time is a chip's; on any other
backend the tool refuses to run (``--backend=cpu`` runs two candidates a
kernel in interpret mode, to rehearse the tool, and prints no time).
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

V5E_BF16_FLOPS = 197e12   # Google Cloud documentation, "TPU v5e"
MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}
BLOCKS = (1024, 2048)     # what a sequence too long for one block goes in
TILES = (128, 256, 512, 1024)


def candidates(fa, kernel, t, d_head, from_rule, tiles, long_blocks):
    """Blocks to time for one kernel at a square shape of length t: the
    sequence whole where it is short, square blocks of it where it is
    long, in every tile of ``tiles`` that divides them."""
    t128 = -(-t // 128) * 128
    blocks = [t128] if t128 <= fa._WHOLE else long_blocks
    seen = []
    for block, tq, tk in itertools.product(blocks, tiles, tiles):
        if block % tq or block % tk:
            continue
        cand = (block, block, tq, tk)
        if fa.vmem_bytes(kernel, cand, d_head) <= fa._VMEM_LIMIT:
            seen.append(cand)
    if from_rule not in seen:
        seen.append(from_rule)
    return seen


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", required=True,
                    help="batch*heads,seq,d_head of bf16 q, k and v")
    ap.add_argument("--heads", type=int, default=0,
                    help="heads a batch row (for --kv_mask; default: all)")
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--kv_mask", action="store_true",
                    help="a key-padding mask, a tenth of the keys padded")
    ap.add_argument("--block_h", type=int, default=0,
                    help="also time the head-folded forward at this fold")
    ap.add_argument("--kernels", default="fwd,dq,dkv")
    ap.add_argument("--blocks", default=",".join(map(str, BLOCKS)),
                    help="square blocks to try where the sequence is long")
    ap.add_argument("--tiles", default=",".join(map(str, TILES)),
                    help="tile sides to try, each way")
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--backend", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--out", default="chiprun_out/flash_sweep")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from dtf_tpu.cli.launch import enable_compile_cache
    from dtf_tpu.ops import flash_attention as fa

    on_chip = args.backend == "tpu"
    if jax.default_backend() != args.backend:
        raise SystemExit(f"flash_sweep: JAX came up on "
                         f"{jax.default_backend()!r}, not {args.backend!r}")
    enable_compile_cache()
    bh, t, d = (int(x) for x in args.shape.split(","))
    heads = args.heads or bh
    dtype = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(kk, (bh, t, d), jnp.float32
                                     ).astype(dtype) for kk in keys)
    bias = None
    if args.kv_mask:
        valid = jnp.arange(t)[None, :] < (t - t // 10)
        bias = fa._mask_bias(jnp.broadcast_to(valid, (bh // heads, t)))
    kw = dict(sm_scale=d ** -0.5, causal=args.causal, window=args.window,
              interpret=not on_chip)
    # residuals for the backward kernels, from the rule's own forward
    rule = fa.flash_blocks(t, t, d, causal=args.causal)
    out, lse = jax.jit(lambda *a: fa._fwd(*a, blocks=rule.fwd, **kw))(
        q, k, v, bias)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)

    def call(kernel, blocks, block_h=1):
        if kernel == "fwd":
            fn = lambda: fa._fwd(q, k, v, bias, blocks=blocks,  # noqa: E731
                                 block_h=block_h, **kw)
        else:
            op = fa._bwd_dq if kernel == "dq" else fa._bwd_dkv
            fn = lambda: op(q, k, v, bias, lse, delta, do,  # noqa: E731
                            blocks=blocks, **kw)
        return jax.jit(fn)

    pairs = t * (t + 1) // 2 if args.causal else t * t
    if args.window:
        pairs = sum(min(i + 1, args.window) for i in range(t))
    rows = []
    for kernel in args.kernels.split(","):
        tile_sides = [int(x) for x in args.tiles.split(",")]
        long_blocks = [int(x) for x in args.blocks.split(",")]
        cands = [(b, 1) for b in candidates(fa, kernel, t, d,
                                             getattr(rule, kernel),
                                             tile_sides, long_blocks)]
        if kernel == "fwd" and args.block_h > 1:
            cands += [((bq, bk, bq, bk), args.block_h)
                      for bq, bk in ((256, 256), (512, 512), (512, 1024))
                      if bq <= t and bk <= t]
        if not on_chip:
            cands = cands[:1] + cands[-1:]
        for blocks, block_h in cands:
            tiles = fa.flash_tiles(t, t, blocks, causal=args.causal,
                                   window=args.window, kernel=kernel)
            bq, bk, tq, tk = blocks
            per_square = (-(-t // bq) * bq // tq) * (-(-t // bk) * bk // tk)
            row = {"kernel": kernel, "blocks": list(blocks),
                   "block_h": block_h,
                   "rule": blocks == getattr(rule, kernel) and block_h == 1,
                   "computed_of_square": len(tiles) / per_square,
                   "masked_of_computed": (sum(m for *_, m in tiles)
                                          / max(len(tiles), 1))}
            try:
                fn = call(kernel, blocks, block_h)
                jax.block_until_ready(fn())
                if on_chip:
                    best = float("inf")
                    for _ in range(3):
                        t0 = time.perf_counter()
                        outs = [fn() for _ in range(args.calls)]
                        jax.block_until_ready(outs)
                        best = min(best,
                                   (time.perf_counter() - t0) / args.calls)
                    flops = 2.0 * MATMULS[kernel] * bh * pairs * d
                    row["ms"] = best * 1e3
                    row["roofline_pct"] = 100 * flops / V5E_BF16_FLOPS / best
            except Exception as e:  # noqa: BLE001 — a refused candidate is a row
                row["error"] = f"{type(e).__name__}: {str(e)[:160]}"
            rows.append(row)
            print(json.dumps(row), flush=True)

    dev = jax.devices()[0]
    record = {"shape": [bh, t, d], "causal": args.causal,
              "window": args.window, "kv_mask": args.kv_mask,
              "device": {"platform": dev.platform, "kind": dev.device_kind},
              "rows": rows}
    os.makedirs(args.out, exist_ok=True)
    name = (f"{bh}x{t}x{d}" + ("_causal" if args.causal else "")
            + (f"_w{args.window}" if args.window else "")
            + ("_mask" if args.kv_mask else ""))
    with open(os.path.join(args.out, name + ".json"), "w") as f:
        json.dump(record, f, indent=1)
    print(f"\n{name} on {dev.device_kind}"
          + ("" if on_chip else " (interpret mode: no times)"))
    print("| kernel | block_q | block_k | tile_q | tile_k | h | ms | "
          "roofline % | computed/square | masked/computed |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    for r in sorted(rows, key=lambda r: (r["kernel"], r.get("ms", 1e9))):
        ms = (f"{r['ms']:.3f} | {r['roofline_pct']:.1f}" if "ms" in r
              else f"{r.get('error', '-')} | -")
        print(f"| {r['kernel']}{' (rule)' if r['rule'] else ''} | "
              + " | ".join(map(str, r["blocks"]))
              + f" | {r['block_h']} | {ms} | "
              f"{r['computed_of_square']:.3f} | "
              f"{r['masked_of_computed']:.3f} |")


if __name__ == "__main__":
    main()
