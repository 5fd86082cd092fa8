"""Host-side step-phase spans — "where did the step time go?".

The reference era answered step-time questions with stdout timestamps; the
TPU-pod literature answers them with a per-phase step breakdown (the
MLPerf-on-pods decomposition of arxiv 1909.09756). This module is the
host half of that story: :class:`SpanRecorder` accumulates named wall-time
samples (``data_wait``, ``h2d``, ``dispatch``, ``hooks`` from the trainer
loop; ``serve_prefill_chunk``/``serve_decode`` from the serve scheduler)
and rolls them up into p50/p99 at report time.

Everything here is ``time.perf_counter`` arithmetic on the host — a span
NEVER touches a device value, so recording cannot introduce a blocking
readback into the sync-free loop (the PR 3 invariant; regression-tested by
tests/test_telemetry.py's counter-instrumented fit).

The device half is :func:`step_annotation`:
``jax.profiler.StepTraceAnnotation`` around each loop iteration stamps the
step number into the XPlane timeline, so a ProfilerHook trace window lines
up 1:1 with the host spans recorded for the same steps. Its serving twin is
:func:`trace_annotation`: the ``dtf.serve.*`` / ``dtf.engine.*`` phases of
a scheduler tick, written into the same timeline.
"""

from __future__ import annotations

import collections
import time
from contextlib import contextmanager, nullcontext
from typing import Iterator, Mapping

from dtf_tpu.metrics import quantile

#: what a gated-off phase enters instead of a :func:`trace_annotation`: one
#: reusable null context, so the untraced hot path constructs nothing
NO_SPAN = nullcontext()

#: per-phase sample retention: enough for tight quantiles over a long run
#: without per-step memory growth (a ring, like the flight recorder).
DEFAULT_KEEP = 4096


class SpanRecorder:
    """Named wall-time samples with bounded memory and p50/p99 rollups.

    ``add(name, seconds)`` is the whole write API (the :meth:`span` context
    manager is sugar over it). Totals/counts are exact over the run; the
    quantiles are computed over the last ``keep`` samples per phase.
    """

    def __init__(self, keep: int = DEFAULT_KEEP, *,
                 clock=time.perf_counter):
        self._keep = keep
        #: injectable monotonic clock (tests assert exact span totals
        #: without real sleeps; analysis host pass: clock-escape)
        self._clock = clock
        self._samples: dict[str, collections.deque] = {}
        self._totals: dict[str, float] = {}
        self._counts: dict[str, int] = {}

    def add(self, name: str, seconds: float) -> None:
        dq = self._samples.get(name)
        if dq is None:
            dq = self._samples[name] = collections.deque(maxlen=self._keep)
            self._totals[name] = 0.0
            self._counts[name] = 0
        dq.append(seconds)
        self._totals[name] += seconds
        self._counts[name] += 1

    @contextmanager
    def span(self, name: str) -> Iterator[None]:
        t0 = self._clock()
        try:
            yield
        finally:
            self.add(name, self._clock() - t0)

    def total(self, name: str) -> float:
        return self._totals.get(name, 0.0)

    def count(self, name: str) -> int:
        return self._counts.get(name, 0)

    def rollup(self) -> Mapping[str, Mapping[str, float]]:
        """``{phase: {count, total_s, mean_s, p50_s, p99_s}}``."""
        out = {}
        for name, dq in self._samples.items():
            xs = list(dq)
            n = self._counts[name]
            out[name] = {
                "count": n,
                "total_s": round(self._totals[name], 6),
                "mean_s": round(self._totals[name] / max(n, 1), 6),
                "p50_s": round(quantile(xs, 0.5), 6),
                "p99_s": round(quantile(xs, 0.99), 6),
            }
        return out


def step_annotation(step: int, name: str = "train"):
    """``jax.profiler.StepTraceAnnotation`` for one loop iteration.

    Imported lazily so :mod:`dtf_tpu.loop` stays jax-free (its
    counter-instrumented tests run the Trainer against fake states with no
    backend at all). The annotation is a host-side TraceMe — nanoseconds
    when no trace is active, and the XPlane step-correlation marker when a
    ProfilerHook window is open.
    """
    import jax

    return jax.profiler.StepTraceAnnotation(name, step_num=step)


def trace_annotation(name: str, **ids):
    """``jax.profiler.TraceAnnotation`` under one of the program's ``dtf.*``
    names (docs/OBSERVABILITY.md section 7 lists them): a host phase on the
    profiler's own clock, beside the device events it caused, so a trace
    reader can say under which phase the device sat idle. Imported lazily
    for the same reason as :func:`step_annotation`: the serve scheduler
    reaches it from here and stays jax-free at import. Callers gate it
    (``Scheduler(telemetry=)``, ``DecodeEngine.annotate_traces``); this
    function always constructs one.
    """
    import jax

    return jax.profiler.TraceAnnotation(name, **ids)
