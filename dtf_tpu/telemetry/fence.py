"""The training compile fence — retraces and backend compiles, counted.

Two complementary counters, the same pair ``DecodeEngine`` pins
(dtf_tpu/serve/engine.py):

- **trace counts**: :meth:`CompileFence.count_traces` wraps the PYTHON
  step function before ``jax.jit`` sees it, so the wrapper body runs once
  per TRACE (not per call). ``make_train_step(..., telemetry=)`` threads
  this through, and ``Trainer.trace_counts`` surfaces it exactly like
  ``DecodeEngine.trace_counts`` — steady state must stay pinned at 1 per
  program; any increment mid-run is a shape/dtype-driven retrace silently
  recompiling the hot path.
- **backend compile events**: a ``jax.monitoring`` listener counting
  compile-related events and summing the ``/jax/core/compile/*_duration``
  durations — this is what feeds the goodput ``compile`` bucket, and it
  catches compiles the trace counter cannot see (helper jits inside hooks,
  donation fallbacks, cache misses).

jax.monitoring offers no unregister API on this jax, so ONE module-level
listener is installed lazily and dispatches to the currently-active fences
— constructing fences per run (tests build many) never stacks listeners.
"""

from __future__ import annotations

import threading

_LOCK = threading.Lock()
_ACTIVE: list["CompileFence"] = []
_INSTALLED = False


def _on_event(name: str, **kw) -> None:
    for f in list(_ACTIVE):
        f._event(name)


def _on_duration(name: str, duration: float, **kw) -> None:
    for f in list(_ACTIVE):
        f._duration(name, duration)


def _install_listeners() -> bool:
    """Register the global dispatchers once. Returns whether monitoring is
    observable on this jax (callers report honestly when it is not)."""
    global _INSTALLED
    with _LOCK:
        if _INSTALLED:
            return True
        import jax

        mon = getattr(jax, "monitoring", None)
        if mon is None or not hasattr(mon, "register_event_listener"):
            return False
        mon.register_event_listener(_on_event)
        if hasattr(mon, "register_event_duration_secs_listener"):
            mon.register_event_duration_secs_listener(_on_duration)
        _INSTALLED = True
        return True


class CompileFence:
    """Per-run trace + compile counters (see module docstring)."""

    def __init__(self):
        #: traces per program name — the ``DecodeEngine.trace_counts`` twin
        self.trace_counts: dict[str, int] = {}
        self.compile_events = 0
        self.compile_s = 0.0
        #: False when jax.monitoring cannot be observed on this jax —
        #: compile_events==0 then means "unobservable", not "no compiles"
        self.monitoring_available = False

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        self.monitoring_available = _install_listeners()
        with _LOCK:
            if self not in _ACTIVE:
                _ACTIVE.append(self)

    def stop(self) -> None:
        with _LOCK:
            if self in _ACTIVE:
                _ACTIVE.remove(self)

    # -------------------------------------------------------- trace counter

    def count_traces(self, name: str, fn):
        """Wrap a to-be-jitted python function so each TRACE increments
        ``trace_counts[name]`` (the DecodeEngine ``counted`` idiom). The
        wrapper is called ``name``: ``jax.jit`` names the program after
        the function it is given, so traces and profiles show
        ``jit_train_step``, not ``jit_wrapped``."""
        self.trace_counts.setdefault(name, 0)

        def wrapped(*args, **kwargs):
            self.trace_counts[name] += 1
            return fn(*args, **kwargs)

        wrapped.__name__ = wrapped.__qualname__ = name
        return wrapped

    # ---------------------------------------------------- event ingestion

    def _event(self, name: str) -> None:
        if "compil" in name:
            self.compile_events += 1

    def _duration(self, name: str, duration: float) -> None:
        if "/compile/" in name:
            self.compile_s += duration

    def snapshot(self) -> tuple[dict, int]:
        """(trace_counts copy, compile event count) — the steady-state
        fence idiom: snapshot after the warm lap, assert flat later."""
        return dict(self.trace_counts), self.compile_events
