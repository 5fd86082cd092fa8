"""Worker for the multi-process graceful-preemption test (run as __main__).

Two processes bootstrap a real 2-device cross-process mesh and train via the
full Trainer/hook stack (CheckpointHook with a huge interval +
PreemptionHook). The parent SIGTERMs BOTH processes mid-run; the hook's
flag OR-allgather makes every host save the SAME step collectively, exit 0,
and a relaunch with a finite step target resumes from the preemption step.
"""

import itertools
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(task_index: int, num_workers: int, port: int, logdir: str,
         target_steps: int) -> None:
    import jax
    import optax

    from dtf_tpu.checkpoint import Checkpointer
    from dtf_tpu.core import train as tr
    from dtf_tpu.core.comms import host_local_to_global
    from dtf_tpu.core.dist import collapse_cluster_flags, initialize
    from dtf_tpu.core.mesh import MeshConfig, make_mesh
    from dtf_tpu.data.synthetic import SyntheticData
    from dtf_tpu.hooks import CheckpointHook, PreemptionHook, StopAtStepHook
    from dtf_tpu.loop import Trainer
    from dtf_tpu.models import mnist

    hosts = [f"localhost:{port + i}" for i in range(num_workers)]
    info = collapse_cluster_flags(worker_hosts=hosts, task_index=task_index)
    initialize(info)
    mesh = make_mesh(MeshConfig())

    model = mnist.make_model("softmax")
    tx = optax.sgd(0.1)
    state, shardings = tr.create_train_state(
        mnist.make_init(model), tx, jax.random.PRNGKey(0), mesh)
    step = tr.make_train_step(mnist.make_loss(model), tx, mesh, shardings)

    data = SyntheticData("mnist", 8 * num_workers, seed=0,
                         host_index=info.process_id,
                         host_count=info.num_processes)
    ckpt = Checkpointer(os.path.join(logdir, "ckpt"))
    trainer = Trainer(
        step, mesh,
        hooks=[CheckpointHook(ckpt, 10 ** 9),   # periodic saves OFF
               PreemptionHook(ckpt),
               StopAtStepHook(target_steps)],
        checkpointer=ckpt,
        place_batch=lambda b: host_local_to_global(b, mesh))
    state = trainer.fit(
        state, (data.batch(i) for i in itertools.count()))
    ckpt.close()
    print(f"done: step={int(state.step)}", flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]),
         sys.argv[4], int(sys.argv[5]))
