#!/usr/bin/env python
"""Per-component device-time attribution WITHOUT jax.profiler.

No device trace of this repo has been read yet (ROADMAP A2), so the
bottleneck question — is a low LM MFU attention's fault, the FFN's, or the
loss's? — gets a first answer without one: each component of the
BERT/GPT step is jitted as its OWN program, XLA's AOT
``compiled.cost_analysis()`` supplies its flops/bytes, and a fenced timing
loop supplies its measured seconds. Components (embed, one attention layer,
one FFN layer, head+loss) extrapolate by layer count and are checked
against the measured full forward / forward+backward / train step — the
`unattributed` residual is the fusion/overhead the component view misses.

Same process contract as bench.py/bench_lm.py: the parent never imports
jax, children run one after the other under the watchdog's budget, and
``BENCH_COST_TABLE.json`` is always written (rows or structured errors).
Runs tiny-config on the CPU sim (logic check, CI-pinned) and real-config on
the TPU.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from _dtf_watchdog import fence as _fence  # host-readback fence
ARTIFACT = os.path.join(ROOT, "BENCH_COST_TABLE.json")
SENTINEL = "BENCH_COST_ROW "
CHILD_TIMEOUT_S = 1500
TOTAL_BUDGET_S = float(os.environ.get("DTF_COST_BUDGET_S", "3600"))


def _cost(fn, *args):
    """(flops, bytes_accessed) from XLA's AOT cost analysis of fn(*args)."""
    # aot-ok: one-shot cost analysis of a bench-local program
    cost = fn.lower(*args).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost.get("flops", 0.0)), float(cost.get(
        "bytes accessed", 0.0))



def _time(fn, *args, iters):
    """Median-free fenced timing: warmup twice (compile + settle), then one
    readback fences ``iters`` queued executions (the bench_lm pattern)."""
    for _ in range(2):
        out = fn(*args)
    _fence(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _fence(out)
    return (time.perf_counter() - t0) / iters


def child():
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import optax
    from flax import linen as nn

    from dtf_tpu.core import train as tr
    from dtf_tpu.core.mesh import MeshConfig, make_mesh
    from dtf_tpu.ops.losses import softmax_cross_entropy
    from dtf_tpu.telemetry.accounting import device_peak_flops

    which = os.environ["DTF_COST_WHICH"]
    tiny = os.environ.get("DTF_COST_TINY") == "1"
    iters = int(os.environ.get("DTF_COST_ITERS", "10"))
    # compile-only: emit the REAL-config AOT cost tables (flops/bytes per
    # component) with no timing loop — runs on the CPU sim, so the
    # flop-share side of the attribution needs no chip.
    compile_only = os.environ.get("DTF_COST_COMPILE_ONLY") == "1"

    def timeit(fn, *args):
        return None if compile_only else _time(fn, *args, iters=iters)
    # Single device throughout: component programs vs the full step must
    # run on the SAME resources for the subtraction to mean anything (and
    # the TPU pool is one chip; on the CPU sim this pins device 0).
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    rng = jax.random.PRNGKey(0)

    class FFN(nn.Module):
        d_ff: int
        d_model: int
        dtype: object

        @nn.compact
        def __call__(self, x):
            y = nn.Dense(self.d_ff, dtype=self.dtype,
                         param_dtype=jnp.float32, name="mlp_in")(x)
            y = nn.gelu(y, approximate=True)
            return nn.Dense(self.d_model, dtype=self.dtype,
                            param_dtype=jnp.float32, name="mlp_out")(y)

    components = {}  # name -> (sec, flops, bytes, layer_multiplier)

    def add(name, module_or_fn, mult, *args):
        if hasattr(module_or_fn, "init"):
            params = module_or_fn.init(rng, *args)
            fn = jax.jit(lambda p, *a: module_or_fn.apply(p, *a))
            args = (params, *args)
        else:
            fn = jax.jit(module_or_fn)
        fl, by = _cost(fn, *args)
        components[name] = (timeit(fn, *args), fl, by, mult)

    if which == "gpt":
        from dtf_tpu.data.synthetic import SyntheticData
        from dtf_tpu.models import gpt

        b = int(os.environ.get("DTF_COST_BATCH", "4" if tiny else "8"))
        s = int(os.environ.get("DTF_COST_SEQ", "64" if tiny else "1024"))
        cfg = gpt.GPTConfig.tiny() if tiny else gpt.GPTConfig.gpt2_small()
        model, init_fn = gpt.make_init(cfg, None, seq_len=s)
        layers, width, d_ff, vocab = (cfg.layers, cfg.d_model, cfg.d_ff,
                                      cfg.vocab_size)
        x = jax.random.normal(rng, (b, s, width), cfg.dtype)
        h_f32 = x.astype(jnp.float32)
        ids = jnp.zeros((b, s), jnp.int32)
        labels = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, vocab)
        add("embed", nn.Embed(vocab, width, dtype=cfg.dtype,
                              param_dtype=jnp.float32), 1, ids)
        # window=0: the full-causal path every layer of the default config
        # runs (the windowed variants have their own ATTN_BENCH rows)
        attn = gpt.CausalSelfAttention(cfg, None, window=0)
        attn_params = attn.init(rng, x, True)
        fnattn = jax.jit(lambda p, a: attn.apply(p, a, True))
        fl, by = _cost(fnattn, attn_params, x)
        components["attn_layer"] = (timeit(fnattn, attn_params, x),
                                    fl, by, layers)
        add("ffn_layer", FFN(d_ff, width, cfg.dtype), layers, x)
        w_head = jax.random.normal(jax.random.PRNGKey(2), (width, vocab),
                                   jnp.float32) * 0.02

        def head_loss(w, h):
            return softmax_cross_entropy(h @ w, labels)[0]

        add("head_loss", head_loss, 1, w_head, h_f32)
        loss_fn = gpt.make_loss(model)
        data = SyntheticData("gpt", b, seed=0, seq_len=s,
                             vocab_size=vocab).batch(0)
    else:
        from dtf_tpu.data.synthetic import SyntheticData
        from dtf_tpu.models import bert

        b = int(os.environ.get("DTF_COST_BATCH", "4" if tiny else "32"))
        s = int(os.environ.get("DTF_COST_SEQ", "64" if tiny else "512"))
        cfg = bert.BertConfig.tiny() if tiny else bert.BertConfig.base()
        model, init_fn = bert.make_init(cfg, None, seq_len=s)
        layers, width, d_ff, vocab = (cfg.layers, cfg.hidden,
                                      cfg.intermediate, cfg.vocab_size)
        x = jax.random.normal(rng, (b, s, width), cfg.dtype)
        h_f32 = x.astype(jnp.float32)
        ids = jnp.zeros((b, s), jnp.int32)
        labels = jnp.where(
            jax.random.uniform(jax.random.PRNGKey(1), (b, s)) < 0.15,
            jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, vocab),
            -100)
        add("embed", nn.Embed(vocab, width, dtype=cfg.dtype,
                              param_dtype=jnp.float32), 1, ids)
        attn = bert.SelfAttention(cfg, None)
        mask = jnp.ones((b, s), bool)
        attn_params = attn.init(rng, x, mask, True)
        fnattn = jax.jit(lambda p, a, m: attn.apply(p, a, m, True))
        fl, by = _cost(fnattn, attn_params, x, mask)
        components["attn_layer"] = (timeit(fnattn, attn_params, x, mask),
                                    fl, by, layers)
        add("ffn_layer", FFN(d_ff, width, cfg.dtype), layers, x)
        w_head = jax.random.normal(jax.random.PRNGKey(2), (width, vocab),
                                   jnp.float32) * 0.02

        def head_loss(w, h):
            return softmax_cross_entropy(h @ w, labels,
                                         ignore_index=-100)[0]

        add("head_loss", head_loss, 1, w_head, h_f32)
        loss_fn = bert.make_loss(model)
        data = SyntheticData("bert", b, seed=0, seq_len=s,
                             vocab_size=vocab).batch(0)

    # whole-program references: fwd, fwd+bwd, full step (same graphs the
    # bench_lm phase decomposition times — here they anchor the residual)
    tx = optax.adamw(1e-4)
    state, shardings = tr.create_train_state(init_fn, tx, rng, mesh)
    step = tr.make_train_step(loss_fn, tx, mesh, shardings)
    data = jax.device_put(data, jax.devices()[0])
    rng0 = jax.random.PRNGKey(0)
    fwd = jax.jit(lambda st, bt: loss_fn(st.params, st.extra, bt, rng0)[0])

    def fwdbwd(st, bt):
        (loss, _), grads = jax.value_and_grad(
            lambda p: loss_fn(p, st.extra, bt, rng0), has_aux=True)(st.params)
        gsum = sum(jnp.sum(jnp.abs(g).astype(jnp.float32))
                   for g in jax.tree.leaves(grads))
        return loss + 1e-30 * gsum  # keep the backward live (bench_lm trick)

    whole = {}
    for name, fn, args in [("fwd", fwd, (state, data)),
                           ("fwdbwd", jax.jit(fwdbwd), (state, data))]:
        fl, by = _cost(fn, *args)
        whole[name] = (timeit(fn, *args), fl, by)
    if compile_only:
        fl, by = _cost(step, state, data)
        whole["step"] = (None, fl, by)
    else:
        t0 = state
        for _ in range(2):
            t0, m = step(t0, data)
        float(m["loss"])
        t_start = time.perf_counter()
        for _ in range(iters):
            t0, m = step(t0, data)
        float(m["loss"])
        whole["step"] = ((time.perf_counter() - t_start) / iters, 0.0, 0.0)

    rows = [{"component": n, "sec": None if sec is None else round(sec, 6),
             "xla_flops": fl, "xla_bytes": by, "x": mult,
             "pct_of_fwd_flops": round(
                 100 * fl * mult / max(whole["fwd"][1], 1.0), 1)}
            for n, (sec, fl, by, mult) in components.items()]
    out = {"model": which, "backend": jax.default_backend(),
           "tiny": tiny, "compile_only": compile_only,
           "batch": b, "seq": s, "layers": layers,
           "components": rows,
           "fwd_flops": whole["fwd"][1],
           "fwdbwd_flops": whole["fwdbwd"][1],
           "step_flops": whole["step"][1]}
    if not compile_only:
        attributed = sum(sec * mult
                         for sec, _, _, mult in components.values())
        for r in rows:
            r["pct_of_fwd"] = round(
                100 * r["sec"] * r["x"] / whole["fwd"][0], 1)
        out.update(
            fwd_sec=round(whole["fwd"][0], 6),
            fwdbwd_sec=round(whole["fwdbwd"][0], 6),
            step_sec=round(whole["step"][0], 6),
            unattributed_fwd_sec=round(whole["fwd"][0] - attributed, 6))
        peak = device_peak_flops()   # a CPU row names no mfu
        if peak:
            out["mfu_fwd_xla"] = round(
                whole["fwd"][1] / whole["fwd"][0] / peak, 4)
    print(SENTINEL + json.dumps(out))


def main():
    from _dtf_watchdog import Budget, child_argv, run_budgeted_jobs

    budget = Budget(TOTAL_BUDGET_S)
    compile_only = os.environ.get("DTF_COST_COMPILE_ONLY") == "1"
    global ARTIFACT
    if compile_only:
        # flop-share tables need no device time: separate artifact
        # (regenerable on the CPU sim)
        ARTIFACT = os.path.join(ROOT, "BENCH_COST_TABLE_AOT.json")
    jobs = [{"DTF_COST_WHICH": "bert"}, {"DTF_COST_WHICH": "gpt"}]
    env_base = dict(os.environ)

    def parse(line):
        if line.startswith(SENTINEL):
            return json.loads(line[len(SENTINEL):])
        return None

    def flush(row, job, rows, errors):
        with open(ARTIFACT, "w") as f:
            json.dump({"rows": rows, "errors": errors,
                       "backend": rows[0].get("backend") if rows else None},
                      f, indent=1)

    rows, errors = run_budgeted_jobs(
        jobs, child_argv(os.path.abspath(__file__)), parse,
        budget=budget, cap_s=CHILD_TIMEOUT_S, env_base=env_base,
        on_result=flush)
    print(json.dumps({"rows": len(rows), "errors": len(errors)}))


if __name__ == "__main__":
    if "--child" in sys.argv:
        child()
    else:
        main()
