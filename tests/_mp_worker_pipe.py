"""Worker for the 2-process pipeline-parallelism test.

Each process owns TWO CPU devices; together they form a (data=2, pipe=2)
mesh, so the GPipe schedule's ``ppermute`` activation hop crosses the
process boundary — the true multi-host seam of pipeline parallelism (on a
pod this hop rides ICI/DCN). Five pipelined GPT-tiny train steps; prints
one "losses: ..." line the parent compares across processes and against a
single-process reference run.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(task_index: int, num_workers: int, port: int) -> None:
    import jax
    import jax.numpy as jnp
    import optax

    from dtf_tpu.core import train as tr
    from dtf_tpu.core.comms import host_local_to_global
    from dtf_tpu.core.dist import collapse_cluster_flags, initialize
    from dtf_tpu.core.mesh import MeshConfig, make_mesh
    from dtf_tpu.data.synthetic import SyntheticData
    from dtf_tpu.models import gpt, gpt_pipe

    hosts = [f"localhost:{port + i}" for i in range(num_workers)]
    info = collapse_cluster_flags(worker_hosts=hosts, task_index=task_index)
    initialize(info)
    assert jax.process_count() == num_workers
    assert jax.device_count() == 2 * num_workers
    mesh = make_mesh(MeshConfig(data=2, pipe=2))

    cfg = gpt.GPTConfig.tiny(attn_impl="dense", dtype=jnp.float32)
    init_fn = gpt_pipe.make_pipe_init(cfg, mesh, seq_len=16)
    tx = optax.sgd(0.1)
    state, shardings = tr.create_train_state(
        init_fn, tx, jax.random.PRNGKey(0), mesh,
        param_rules=gpt_pipe.pipe_rules(), zero1=False)
    step = tr.make_train_step(
        gpt_pipe.make_pipe_loss(cfg, mesh, n_microbatches=4), tx, mesh,
        shardings, log_grad_norm=False)

    data = SyntheticData("gpt", 16, seed=0, seq_len=16,
                         vocab_size=cfg.vocab_size,
                         host_index=info.process_id,
                         host_count=info.num_processes)
    losses = []
    for i in range(5):
        state, metrics = step(state, host_local_to_global(data.batch(i), mesh))
        losses.append(float(metrics["loss"]))
    print("losses: " + " ".join(f"{l:.6f}" for l in losses), flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]))
