"""Device idle time under one of the program's host phases, per tick.

The program writes its host phases into the profiler's own timeline as
``dtf.*`` spans (``jax.profiler.TraceAnnotation``; docs/OBSERVABILITY.md
section 7 names them), so the question "the chip sat idle while the host
did what?" is interval arithmetic on one clock: in the steady window of the
first device plane, the idle intervals (where no ``XLA Ops`` event ran) cut
with the union of the host spans whose name matches ``inside``, less the
union of those matching ``outside``; summed, and divided by the number of
spans matching ``per`` that start in the window. In seconds x ``scale``.

Nothing is guessed: no span is chosen for a gap by its middle or by being
the shortest. An idle interval that straddles two spans is split between
them; one that no ``inside`` span covers is counted nowhere (the benchmark's
clients between ticks). The window begins and ends inside a tick, so the
first tick's idle tail is counted though the tick is not, and the last
tick is counted though its tail is cut: with some tens of ticks the two
ends cancel to well under a tick's share.

Host and device events share the trace's clock only to within a couple of
milliseconds, and differently from run to run (PERF.md section 6 has the
measured bound): a reading under that is zero for all this reader can tell,
and what one phase loses to the skew its neighbour gains, so neighbouring
phases are judged by their sum.

Returns nothing without a trace, without a device plane, or where no
``per`` span starts in the window (a program that writes no such span).
"""

import re

from benchmarks.lib import xtrace


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals (what :func:`xtrace.merge` returns)."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(obs, *, inside: str, per: str, outside: str = None,
         scale: float = 1.0):
    trace = obs.get("trace")
    if trace is None or not trace.modules:
        return None
    plane = min(trace.modules)
    window = xtrace.steady_window(trace.modules[plane])
    rx_per = re.compile(per)
    n_per = sum(1 for name, start, _ in trace.host
                if window[0] <= start < window[1] and rx_per.search(name))
    if not n_per:
        return None
    rx_in = re.compile(inside)
    covered = xtrace.merge(xtrace.clip(
        ((s, s + d) for name, s, d in trace.host if rx_in.search(name)),
        window))
    # the idle intervals, less the `outside` spans: what neither an
    # instruction nor such a span occupies
    occupied = trace.ops.get(plane, [])
    if outside is not None:
        rx_out = re.compile(outside)
        occupied = occupied + [e for e in trace.host if rx_out.search(e[0])]
    idle = xtrace.gaps(occupied, window)
    return overlap_ns(idle, covered) / 1e9 / n_per * scale
