"""Worker for the 2-process x 2-device TP/ZeRO-1/checkpoint test.

Each process owns TWO CPU devices; together they form a (data=2, model=2)
mesh, so the Megatron TP collectives AND the ZeRO-1 optimizer-state shards
cross the process boundary. Five BERT-tiny train steps with a cross-host
Orbax sharded save after step 3, a restore into a FRESH state, then two more
steps — printing one "losses: ..." line the parent compares across processes
and against a single-process reference run (proving the restore reproduced
the exact state, not just a similar one).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(task_index: int, num_workers: int, port: int, ckpt_dir: str) -> None:
    import jax
    import optax

    from dtf_tpu.checkpoint import Checkpointer
    from dtf_tpu.core import train as tr
    from dtf_tpu.core.comms import host_local_to_global
    from dtf_tpu.core.dist import collapse_cluster_flags, initialize
    from dtf_tpu.core.mesh import MeshConfig, make_mesh
    from dtf_tpu.data.synthetic import SyntheticData
    from dtf_tpu.models import bert

    hosts = [f"localhost:{port + i}" for i in range(num_workers)]
    info = collapse_cluster_flags(worker_hosts=hosts, task_index=task_index)
    initialize(info)
    assert jax.process_count() == num_workers
    assert jax.device_count() == 2 * num_workers
    mesh = make_mesh(MeshConfig(data=2, model=2))

    cfg = bert.BertConfig.tiny()
    seq_len = 16
    model, init_fn = bert.make_init(cfg, None, seq_len=seq_len)
    tx = optax.adam(1e-3)

    def build():
        return tr.create_train_state(init_fn, tx, jax.random.PRNGKey(0),
                                     mesh, param_rules=bert.tp_rules,
                                     zero1=True)

    state, shardings = build()
    step = tr.make_train_step(bert.make_loss(model), tx, mesh, shardings)

    data = SyntheticData("bert", 8, seed=0, seq_len=seq_len,
                         vocab_size=cfg.vocab_size,
                         host_index=info.process_id,
                         host_count=info.num_processes)
    ckpt = Checkpointer(ckpt_dir, async_save=False)
    losses = []
    for i in range(3):
        state, metrics = step(state, host_local_to_global(data.batch(i), mesh))
        losses.append(float(metrics["loss"]))
    ckpt.save(3, state, force=True)
    ckpt.wait()

    # fresh state, cross-host sharded restore, continue
    fresh, _ = build()
    state = ckpt.restore(fresh)
    assert int(state.step) == 3
    for i in range(3, 5):
        state, metrics = step(state, host_local_to_global(data.batch(i), mesh))
        losses.append(float(metrics["loss"]))
    ckpt.close()
    print("losses: " + " ".join(f"{l:.6f}" for l in losses), flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])
