"""Worker program for the multi-process distributed test (run as __main__).

Each process: collapse worker flags → jax.distributed.initialize (TSL
coordination service) → 2-device global mesh (1 CPU device per process) →
5 MNIST-softmax train steps with host-local batches assembled into global
arrays. Prints one "losses: ..." line the parent test compares across
processes and against a single-process reference run.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(task_index: int, num_workers: int, port: int) -> None:
    import jax
    import optax

    from dtf_tpu.core import train as tr
    from dtf_tpu.core.comms import host_local_to_global
    from dtf_tpu.core.dist import collapse_cluster_flags, initialize
    from dtf_tpu.core.mesh import MeshConfig, make_mesh
    from dtf_tpu.data.synthetic import SyntheticData
    from dtf_tpu.models import mnist

    hosts = [f"localhost:{port + i}" for i in range(num_workers)]
    info = collapse_cluster_flags(worker_hosts=hosts, task_index=task_index)
    initialize(info)
    assert jax.process_count() == num_workers
    mesh = make_mesh(MeshConfig())

    model = mnist.make_model("softmax")
    tx = optax.sgd(0.1)
    state, shardings = tr.create_train_state(
        mnist.make_init(model), tx, jax.random.PRNGKey(0), mesh)
    step = tr.make_train_step(mnist.make_loss(model), tx, mesh, shardings)

    data = SyntheticData("mnist", 8 * num_workers, seed=0,
                         host_index=info.process_id,
                         host_count=info.num_processes)
    losses = []
    for i in range(5):
        batch = host_local_to_global(data.batch(i), mesh)
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    print("losses: " + " ".join(f"{l:.6f}" for l in losses), flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]))
