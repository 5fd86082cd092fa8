"""From a profiler trace to busy time, idle gaps and per-op time.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``jax.profiler.ProfileData`` reads it with nothing but JAX. What a v5e trace
holds (first read in PR 23; that trace is the tests' fixture,
``tests/benchmark_suite/data/v5e_probe.xplane.pb``):

- one plane ``/device:TPU:<n>`` per chip, with the lines ``XLA Modules``
  (one event per executed program, named ``jit_<fn>(<hash>)``), ``XLA Ops``
  (one event per executed HLO instruction, named by the instruction's whole
  text: ``%fusion.3 = bf16[...] fusion(...)``), ``Steps`` and
  ``Async XLA Ops``;
- a plane ``/host:CPU`` with one line per thread; ``TraceAnnotation`` and
  ``StepTraceAnnotation`` spans are events on the Python thread's line.

Times are nanoseconds on the trace's own clock. Host and device events are
on that one clock only to about a millisecond: in the first trace a program
started on the device 1.4 ms *before* the host event that enqueued it.

Everything below the loader works on plain tuples, so the arithmetic is
tested on hand-made lists.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Iterable, Optional, Sequence

Event = tuple[str, float, float]          # name, start_ns, duration_ns
Interval = tuple[float, float]            # start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    """One trace, reduced to what the readers use."""

    #: per device plane, the executed instructions and programs
    ops: dict[str, list[Event]]
    modules: dict[str, list[Event]]
    #: annotation spans of the host's Python threads (frames left out)
    host: list[Event]


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``trace_dir``, or None."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def start() -> str:
    """Start the profiler on a new directory under ``TMPDIR``; annotations
    and device events only, no Python frames (they slow the host that the
    idle share is meant to measure)."""
    import jax

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    return trace_dir


def stop(trace_dir: str) -> Optional["Trace"]:
    """Stop the profiler, read what it wrote and remove the directory."""
    import jax

    jax.profiler.stop_trace()
    try:
        path = find_xplane(trace_dir)
        return load(path) if path else None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` (JAX only; no TensorFlow)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    trace = Trace(ops={}, modules={}, host=[])
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    events = [(e.name, float(e.start_ns), float(e.duration_ns))
                              for e in line.events]
                    target = (trace.ops if line.name == OPS_LINE
                              else trace.modules)
                    target[plane.name] = events
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                # annotations are written by Python threads; the runtime's
                # own threads ("tfrt-...", "futex-...") hold its C++ spans
                if not line.name.startswith("python"):
                    continue
                trace.host.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    # "$file.py:12 fn" is a Python frame, not an annotation
                    if not e.name.startswith("$") and e.duration_ns > 0)
    return trace


# ------------------------------------------------------------- arithmetic


def merge(intervals: Iterable[Interval]) -> list[Interval]:
    """The union of ``intervals`` as sorted, disjoint intervals."""
    out: list[list[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], window: Interval) -> list[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_ns(events: Sequence[Event], window: Interval) -> float:
    """Nanoseconds of ``window`` in which at least one event ran."""
    spans = clip(((s, s + d) for _, s, d in events), window)
    return sum(e - s for s, e in merge(spans))


def steady_window(modules: Sequence[Event]) -> Interval:
    """From the start of the first executed program to the end of the last:
    the traced slice without the idle head and tail that starting and
    stopping the profiler leave round it."""
    return (min(s for _, s, _ in modules),
            max(s + d for _, s, d in modules))


def gaps(events: Sequence[Event], window: Interval) -> list[Interval]:
    """The idle intervals of ``window``: where no event ran."""
    busy = merge(clip(((s, s + d) for _, s, d in events), window))
    out, cursor = [], window[0]
    for start, end in busy:
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if window[1] > cursor:
        out.append((cursor, window[1]))
    return out


def label_gap(gap: Interval, host: Sequence[Event]) -> str:
    """What the host was doing in ``gap``: the shortest annotation span that
    covers the gap's middle, or ``"(no host span)"``."""
    mid = (gap[0] + gap[1]) / 2.0
    covering = [(d, name) for name, s, d in host if s <= mid < s + d]
    return min(covering)[1] if covering else "(no host span)"


_OP_NAME = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?\s*=")
_CUSTOM_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_FUSION_KIND = re.compile(r"\bkind=k(\w+)")


def op_kind(text: str) -> str:
    """A short, stable-ish label for one instruction's text: its name
    without the trailing ``.N``, plus the target of a custom call.
    ``%fusion.12 = ...`` -> ``fusion``; a Pallas kernel ->
    ``jvp__[tpu_custom_call]``; a fusion XLA gave no better name than
    ``fusion`` -> ``fusion[Output]`` by its kind (Output: a matmul or
    convolution with what was fused behind it; Loop: elementwise; Input: a
    reduction). Instructions that differ only in their number add up under
    one label."""
    m = _OP_NAME.match(text)
    kind = m.group(1) if m else text.split(" ", 1)[0][:60]
    detail = _CUSTOM_TARGET.search(text) or (
        _FUSION_KIND.search(text) if kind == "fusion" else None)
    return f"{kind}[{detail.group(1)}]" if detail else kind


#: instructions that only contain others (a ``lax.scan`` is one ``while``
#: event that spans every instruction of its body): busy time counts them
#: once through the union, a ranking by time would count their bodies twice
CONTAINERS = frozenset({"while", "conditional", "call"})


def top(pairs: Iterable[tuple[str, float]], k: int = 10
        ) -> list[list]:
    """Sum seconds by label; the ``k`` largest, largest first."""
    total: dict[str, float] = {}
    for label, seconds in pairs:
        total[label] = total.get(label, 0.0) + seconds
    ranked = sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))
    return [[label, seconds] for label, seconds in ranked[:k]]


# ------------------------------------------------------------- reductions


def device_summary(trace: Trace) -> Optional[dict]:
    """``busy_s`` and ``window_s`` averaged over the chips, and the
    breakdown: the instructions that took most device time (summed by
    :func:`op_kind`) and the idle time by what the host was doing."""
    planes = [p for p in sorted(trace.ops) if trace.modules.get(p)]
    if not planes:
        return None
    busy, window_len, op_pairs, gap_pairs = [], [], [], []
    for plane in planes:
        window = steady_window(trace.modules[plane])
        ops = trace.ops[plane]
        busy.append(busy_ns(ops, window) / 1e9)
        window_len.append((window[1] - window[0]) / 1e9)
        for name, start, dur in ops:
            kind = op_kind(name)
            if window[0] <= start < window[1] and kind not in CONTAINERS:
                op_pairs.append((kind, dur / 1e9 / len(planes)))
        for gap in gaps(ops, window):
            gap_pairs.append((label_gap(gap, trace.host),
                              (gap[1] - gap[0]) / 1e9 / len(planes)))
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": sum(window_len) / len(window_len),
        "breakdown": {"device_ops": top(op_pairs),
                      "idle_gaps": top(gap_pairs)},
    }


def module_durations(trace: Trace, pattern: Optional[str] = None
                     ) -> list[float]:
    """Seconds of each execution of one program on the first device: the
    program whose name matches ``pattern``, or else the one that took most
    time in all."""
    if not trace.modules:
        return []
    events = trace.modules[min(trace.modules)]
    if pattern is not None:
        return [d / 1e9 for n, _, d in events if re.search(pattern, n)]
    totals: dict[str, float] = {}
    for name, _, dur in events:
        totals[name] = totals.get(name, 0.0) + dur
    dominant = max(totals, key=totals.get)
    return [d / 1e9 for n, _, d in events if n == dominant]


def op_seconds(trace: Trace, pattern: str) -> tuple[float, int]:
    """Total seconds and count, on the first device and inside the steady
    window, of the instructions whose text matches ``pattern``."""
    if not trace.modules:
        return 0.0, 0
    plane = min(trace.modules)
    window = steady_window(trace.modules[plane])
    rx = re.compile(pattern)
    hits = [d for n, s, d in trace.ops.get(plane, [])
            if window[0] <= s < window[1] and rx.search(n)]
    return sum(hits) / 1e9, len(hits)
