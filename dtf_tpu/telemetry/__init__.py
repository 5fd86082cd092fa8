"""Run-wide observability: step-phase spans, MFU/goodput accounting, the
training compile fence, and the crash flight recorder (docs/OBSERVABILITY.md).

Enable with ``--telemetry`` on any train launcher; programmatic use:

    tel = Telemetry(out_dir=...)
    step = make_train_step(..., telemetry=tel)
    Trainer(step, mesh, hooks=..., telemetry=tel).fit(state, batches)
    print(json.dumps(tel.finish()))      # the one RunReport JSON line
"""

from dtf_tpu.telemetry.accounting import (DEVICE_PEAKS,            # noqa: F401
                                          GoodputTracker,
                                          RESNET50_TRAIN_FLOPS_PER_IMG,
                                          analytic_lm_flops_per_step,
                                          cost_analysis_flops,
                                          device_peak_flops, device_peaks,
                                          param_count)
from dtf_tpu.telemetry.events import EventLog, read_events         # noqa: F401
from dtf_tpu.telemetry.fence import CompileFence                   # noqa: F401
from dtf_tpu.telemetry.flight import (FlightRecorder,              # noqa: F401
                                      StallWatchdog)
from dtf_tpu.telemetry.run import Telemetry, merge_artifact        # noqa: F401
from dtf_tpu.telemetry.spans import (SpanRecorder,             # noqa: F401
                                     step_annotation, trace_annotation)
from dtf_tpu.telemetry.trace import TraceCollector                 # noqa: F401

# NOTE: dtf_tpu.telemetry.xplane / .profile are imported lazily by their
# consumers (ProfilerHook, the report CLI) — they must
# stay importable without jax OR tensorflow (srclint lazy-import fence).
