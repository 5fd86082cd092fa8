"""A training cell: the program's step under ``Trainer.fit``, one window.

The recipe is the one ``scripts/bench_lm.py``'s ``child()`` had for building
a step in-process (``make_init`` -> ``create_train_state`` -> ``make_loss``
-> ``make_train_step``, ``SyntheticData``), put under ``Trainer.fit`` with
the default prefetch depth, so the window times the job and not the bare
step. No launcher, no flags, no checkpoint, no eval hook.

Traffic file keys (``"kind": "train"``): ``batch`` (sequences a step),
``seq_len``, ``grad_accum``, ``fence_every`` (steps between readbacks of the
loss, as a launcher's ``--log_every``), ``mesh`` (axis sizes), ``optimizer``
(``lr``, ``weight_decay``), ``warmup_steps``, ``check_seq_len``,
``trace_steps``, and ``rehearse`` (overrides for a CPU rehearsal).
"""

from __future__ import annotations

import importlib
import math
import time

import jax
import optax

from benchmarks.lib import xtrace
from benchmarks.lib.flops import matmul_params
from benchmarks.lib.stats import percentile
from dtf_tpu.core import train as tr
from dtf_tpu.core.comms import shard_batch
from dtf_tpu.core.mesh import MeshConfig, make_mesh
from dtf_tpu.data.synthetic import SyntheticData
from dtf_tpu.hooks import Hook, StopTraining
from dtf_tpu.loop import Trainer
from dtf_tpu.telemetry import Telemetry


class WindowHook(Hook):
    """The benchmark's hook: the fences, the window's end, the traced slice.

    A fence is the readback a launcher's ``LoggingHook`` makes every
    ``--log_every`` steps: the host waits there for every step dispatched so
    far. The window ends at the first fence past ``seconds``; with
    ``trace_steps`` a slice of that many steps then runs under the profiler,
    after the window, so tracing cannot touch what the window measured.
    """

    def __init__(self, *, seconds: float, fence_every: int, tel: Telemetry,
                 trace_steps: int, memory):
        self.seconds, self.fence_every = seconds, fence_every
        self.tel, self.trace_steps = tel, trace_steps
        self.memory = memory          # read at the window's two edges
        self.fences = []              # (seconds, steps completed)
        self.losses = []
        self.steps = 0
        self.spans = {}               # the window's span roll-up
        self.trace = None             # the traced slice, read back
        self._trace_dir = None
        self._trace_until = None

    def begin(self, state):
        jax.block_until_ready(state)
        self.memory.sample()
        self.fences.append((time.perf_counter(), 0))

    def _fence(self, metrics) -> float:
        self.losses.append(float(metrics["loss"]))
        return time.perf_counter()

    def after_step(self, step, state, metrics):
        self.steps += 1
        if self._trace_until is not None:
            if self.steps >= self._trace_until:
                self._fence(metrics)
                self.trace = xtrace.stop(self._trace_dir)
                raise StopTraining
            return
        if self.steps % self.fence_every:
            return
        now = self._fence(metrics)
        self.fences.append((now, self.steps))
        if now - self.fences[0][0] < self.seconds:
            return
        self.memory.sample()
        self.spans = self.tel.spans.rollup()
        if not self.trace_steps:
            raise StopTraining
        self._trace_dir = xtrace.start()
        self._trace_until = self.steps + self.trace_steps


def run(job) -> dict:
    config, traffic = job.config, job.traffic
    laps = [("start", job.t_setup)]   # set-up's phases, from run.py's last
    batch, seq_len = traffic["batch"], traffic["seq_len"]
    accum = traffic["grad_accum"]
    mesh = make_mesh(MeshConfig(**traffic.get("mesh", {})),
                     devices=jax.devices()[:job.chips])
    family = importlib.import_module(
        f"benchmarks.families.{config['family']}")
    fam = family.build_train(config, batch=batch // accum, seq_len=seq_len,
                             mesh=mesh)

    tx = optax.adamw(traffic["optimizer"]["lr"],
                     weight_decay=traffic["optimizer"]["weight_decay"])
    # weights on the device from the seed, in one jitted call; zero1 shards
    # the optimizer state over the data axis (a no-op on one chip)
    state, shardings = tr.create_train_state(
        fam.init_fn, tx, jax.random.PRNGKey(job.seed), mesh,
        param_rules=fam.rules, zero1=True)
    n_params = matmul_params(state.params)
    n_matmul = matmul_params(state.params, fam.lookup_only)
    jax.block_until_ready(state)
    laps.append(("init_s", time.perf_counter()))

    check = fam.check(state.params, job.seed, traffic["check_seq_len"])
    laps.append(("check_s", time.perf_counter()))

    tel = Telemetry(watchdog=False)
    step = tr.make_train_step(fam.loss_fn, tx, mesh, shardings,
                              grad_accum=accum, telemetry=tel)
    data = SyntheticData(fam.data_kind, batch, seed=job.seed,
                         seq_len=seq_len, vocab_size=fam.vocab_size)
    # warm the one shape the window uses
    for i in range(traffic["warmup_steps"]):
        state, metrics = step(state, shard_batch(data.batch(i), mesh))
    jax.block_until_ready(state)

    hook = WindowHook(
        seconds=job.seconds, fence_every=traffic["fence_every"], tel=tel,
        trace_steps=traffic["trace_steps"] if job.trace else 0,
        memory=job.memory)
    Trainer(step, mesh, hooks=[hook], telemetry=tel).fit(state, iter(data))
    # warm-up runs on to the window's first fence: set-up ends there
    laps.append(("warmup_s", hook.fences[0][0]))

    # seconds a step between neighbouring fences: one long interval is a
    # stall, all of them long is a slow device
    per_step = [(t1 - t0) / (n1 - n0) for (t0, n0), (t1, n1)
                in zip(hook.fences, hook.fences[1:])]
    bad = sum(1 for x in hook.losses if not math.isfinite(x))
    window_steps = hook.fences[-1][1]
    # the step was traced once, at warm-up: nothing compiled in the window
    traces = tel.trace_counts.get("train_step")
    return {
        "correct": (bool(check["ok"]) and bad == 0 and window_steps > 0
                    and traces == 1),
        "attempted": window_steps,
        "failed": bad,
        "window_start": hook.fences[0][0],
        "series": {"tokens_done": [(t, n * batch * seq_len)
                                   for t, n in hook.fences]},
        "spans": hook.spans,
        "trace": hook.trace,
        # each number `correct` compares, as (reading, limit)
        "limits": {"loss_abs_err": (check["loss_abs_err"],
                                    check["loss_abs_tol"]),
                   "logit_rel_rms_err": (check["logit_rel_rms_err"],
                                         check["logit_rel_rms_tol"]),
                   "nonfinite_losses": (bad, 0),
                   "train_step_traces": (traces, 1)},
        "values": {
            "n_params": n_params, "n_matmul_params": n_matmul,
            "layers": fam.layers, "width": fam.width,
            "seq_len": seq_len, "grad_accum": accum,
            "device_micro_batch": batch // accum // mesh.shape["data"],
            "attention": fam.attention,
            **{f"setup_{name}": t - t_before
               for (_, t_before), (name, t) in zip(laps, laps[1:])},
        },
        "notes": {"check": check, "loss_path": fam.loss_path,
                  "params": {"all": n_params, "in_matmuls": n_matmul},
                  "train_step_traces": traces,
                  "step_ms_between_fences": {
                      "min": round(1e3 * min(per_step), 3),
                      "p50": round(1e3 * percentile(per_step, 50), 3),
                      "max": round(1e3 * max(per_step), 3)},
                  "first_loss": hook.losses[0] if hook.losses else None,
                  "last_loss": hook.losses[-1] if hook.losses else None},
    }
