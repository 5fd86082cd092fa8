"""Compile a train cell's step at its real size for a DESCRIBED v5e.

No chip is needed: the TPU's compiler is installed here and compiles for a
chip that is described and not attached. It refuses what the chip would
refuse (a program that does not fit, a kernel off the tiling) and prints the
bytes the step needs, which is how a cell is sized before it costs chip time.
A compile that passes is a compile, never a measurement.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_v5e.py \
        --workload bert-base-train-b256s512 [--grad_accum 8]
"""

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--grad_accum", type=int, default=None,
                    help="try another accumulation than the traffic file's")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dtf_tpu.core import train as tr
    from dtf_tpu.core.mesh import MeshConfig, make_mesh

    from benchmarks.run import by_name, load_json

    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = by_name(manifest["workloads"], args.workload, "workload")
    config = load_json(ROOT, by_name(manifest["configs"], cell["config"],
                                     "config")["file"])
    traffic = load_json(ROOT, "benchmarks", "traffic",
                        cell["traffic"] + ".json")
    accum = args.grad_accum or traffic["grad_accum"]
    batch, seq_len = traffic["batch"], traffic["seq_len"]

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # code that asks the backend takes its TPU branch (the flash kernel,
    # compiled and not interpreted): steered here, not by an option of the
    # program
    jax.default_backend = lambda: "tpu"
    mesh = make_mesh(MeshConfig(**traffic.get("mesh", {})),
                     devices=topo.devices[:cell["chips"]])
    family = importlib.import_module(
        f"benchmarks.families.{config['family']}")
    fam = family.build_train(config, batch=batch // accum, seq_len=seq_len,
                             mesh=mesh)
    tx = optax.adamw(traffic["optimizer"]["lr"],
                     weight_decay=traffic["optimizer"]["weight_decay"])
    abstract, shardings = tr.abstract_train_state(
        fam.init_fn, tx, jax.random.PRNGKey(0), mesh,
        param_rules=fam.rules, zero1=True)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shardings)
    step = tr.make_train_step(fam.loss_fn, tx, mesh, shardings,
                              grad_accum=accum)

    from dtf_tpu.data.synthetic import SyntheticData

    example = SyntheticData(fam.data_kind, batch, seed=0, seq_len=seq_len,
                            vocab_size=fam.vocab_size).batch(0)
    data = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, jnp.asarray(x[:1]).dtype,
            sharding=NamedSharding(mesh, P("data"))), example)
    t0 = time.perf_counter()
    compiled = step.lower(state, data).compile()
    m = compiled.memory_analysis()
    gib = 2.0 ** 30
    print(json.dumps({
        "workload": args.workload, "grad_accum": accum,
        "micro_batch": batch // accum, "compile_s": round(
            time.perf_counter() - t0, 1),
        "argument_gib": round(m.argument_size_in_bytes / gib, 3),
        "output_gib": round(m.output_size_in_bytes / gib, 3),
        "alias_gib": round(m.alias_size_in_bytes / gib, 3),
        "temp_gib": round(m.temp_size_in_bytes / gib, 3),
        "arguments_plus_temp_gib": round(
            (m.argument_size_in_bytes + m.temp_size_in_bytes) / gib, 3),
        "pallas_calls": compiled.as_text().count("tpu_custom_call"),
        "loss_path": fam.loss_path}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
