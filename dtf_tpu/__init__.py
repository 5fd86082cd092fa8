"""dtf_tpu — a TPU-native distributed training framework.

A ground-up JAX/XLA/pjit/Pallas re-design of the capability surface of the
reference repo ``zjj2wry/distributed-tensorflow`` (a TF1 parameter-server /
worker training harness; see SURVEY.md for the full structural analysis).

The reference's ps/worker roles collapse into a single pjit'd train step over
a TPU device mesh:

- variable placement (``tf.device('/job:ps')`` + ``replica_device_setter``)
  → GSPMD ``NamedSharding`` over a named mesh       → :mod:`dtf_tpu.core.mesh`,
    :mod:`dtf_tpu.core.sharding`
- gradient aggregation (``SyncReplicasOptimizer``) → mean-gradients via XLA
  all-reduce over ICI                               → :mod:`dtf_tpu.core.train`
- ``MonitoredTrainingSession`` hooks (checkpoint / summary / recovery)
  → Orbax + metric writers + a hook-driven loop     → :mod:`dtf_tpu.loop`,
    :mod:`dtf_tpu.checkpoint`, :mod:`dtf_tpu.metrics`
- ``ClusterSpec`` / ``tf.train.Server`` bootstrap   → ``jax.distributed`` +
  mesh construction                                 → :mod:`dtf_tpu.core.dist`
"""

__version__ = "0.1.0"

try:
    import jax as _jax  # noqa: F401
except ImportError:
    # Backend-less machine: the training/serving stack is unusable, but
    # dtf_tpu.telemetry's XPlane parser and report CLI must still import
    # (traces are captured on a chip and analyzed wherever convenient —
    # the srclint lazy-import fence keeps those modules jax/tf-free, and
    # tests/test_analysis.py proves the no-backend import path works).
    HAVE_JAX = False
else:
    HAVE_JAX = True
    from dtf_tpu.core.mesh import MeshConfig, make_mesh, AXIS_DATA, AXIS_SEQ, AXIS_MODEL  # noqa: F401,E501
