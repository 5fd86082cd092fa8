"""The compile fence — retraces and backend compiles counted, and what each
program's compile cost.

Three accounts, the first per run and the other two from ONE process-wide
``jax.monitoring`` listener:

- **trace counts**: :meth:`CompileFence.count_traces` wraps the PYTHON
  step function before ``jax.jit`` sees it, so the wrapper body runs once
  per TRACE (not per call). ``make_train_step(..., telemetry=)`` threads
  this through, and ``Trainer.trace_counts`` surfaces it exactly like
  ``DecodeEngine.trace_counts`` — steady state must stay pinned at 1 per
  program; any increment mid-run is a shape/dtype-driven retrace silently
  recompiling the hot path.
- **backend compiles**, while a fence is started: ``compile_events`` counts
  the executables XLA compiled or loaded from the persistent cache (JAX
  wraps both in ``/jax/core/compile/backend_compile_duration``), one per
  executable, of any program — it catches compiles the trace counter cannot
  see (helper jits inside hooks, donation fallbacks). ``compile_s`` sums
  the three compile phases (trace, lower to MLIR, backend compile or load)
  at the top level only: a phase that runs inside another on the same
  thread (a nested jit's trace inside its parent's, an eager op compiled
  while a step traces) is already in its parent's duration. It feeds the
  goodput ``compile`` bucket. The ``/jax/compilation_cache/*`` events are
  the cache's bookkeeping, not compiles, and are not counted.
- **per-program compile records** (:func:`program_compile`): for each
  program compiled at the top level, keyed by the name of the function it
  traced, its latest compile's trace, lower and compile-or-load seconds
  and whether the persistent cache served it (``cache_hit`` 1.0 where a
  ``cache_hits`` event came while its backend compile was in flight, 0.0
  where ``cache_misses`` or nothing came). Only a program's own events
  count: ``fun_name`` equal to its name (the trace) or ``jit(<name>)``
  (lower and compile). :mod:`dtf_tpu.core.executor` installs the listener
  at import, so programs that compile before any ``Telemetry`` exists (the
  serve engine's) have their records;
  :meth:`dtf_tpu.telemetry.run.Telemetry.file_compiles` puts them on the
  span channel.

The listener runs only when JAX compiles, so no hot path pays for it.
jax.monitoring offers no unregister API on every jax, so the listener is
installed once and dispatches to the currently-active fences —
constructing fences per run (tests build many) never stacks listeners.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Optional

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

#: the three compile phases, by event, and the record field each fills
_PHASES = {TRACE_EVENT: "trace_s", LOWER_EVENT: "lower_s",
           COMPILE_EVENT: "compile_s"}

_LOCK = threading.Lock()
_ACTIVE: list["CompileFence"] = []
_INSTALLED = False
#: per compiling thread: how many compile phases are open (JAX records a
#: scalar at a phase's start and its duration at its end), and the cache's
#: verdict on the backend compile in flight
_LOCAL = threading.local()


@dataclasses.dataclass
class ProgramCompile:
    """One compile of one program: seconds by phase, and 1.0 where the
    persistent cache served the executable."""

    trace_s: float = 0.0
    lower_s: float = 0.0
    compile_s: float = 0.0
    cache_hit: float = 0.0


#: top-level phases seen for a program whose backend compile has not come
_OPEN: dict[str, ProgramCompile] = {}
#: per program, (compiles so far, the latest compile)
_DONE: dict[str, tuple[int, ProgramCompile]] = {}


def program_compile(key: str) -> Optional[tuple[int, ProgramCompile]]:
    """``(compiles so far, the latest)`` of the program whose traced
    function is named ``key``; None before its first compile."""
    return _DONE.get(key)


def _program_key(fun_name: str) -> str:
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _on_scalar(name: str, value, **kw) -> None:
    if name in _PHASES:
        _LOCAL.depth = getattr(_LOCAL, "depth", 0) + 1
        if name == COMPILE_EVENT:
            _LOCAL.cache_hit = 0.0


def _on_event(name: str, **kw) -> None:
    if name == CACHE_HIT_EVENT:
        _LOCAL.cache_hit = 1.0
    elif name == CACHE_MISS_EVENT:
        _LOCAL.cache_hit = 0.0


def _on_duration(name: str, duration: float, **kw) -> None:
    field = _PHASES.get(name)
    if field is None:
        return
    depth = max(getattr(_LOCAL, "depth", 1) - 1, 0)
    _LOCAL.depth = depth
    top = depth == 0
    for f in list(_ACTIVE):
        f._duration(name, duration, top)
    fun_name = kw.get("fun_name")
    if not top or fun_name is None:
        return
    key = _program_key(fun_name)
    with _LOCK:
        rec = _OPEN.get(key)
        if rec is None or (name == TRACE_EVENT and rec.lower_s):
            # a lowering that never compiled (an analysis view, a test)
            # leaves no phase in the next compile's record
            rec = _OPEN[key] = ProgramCompile()
        setattr(rec, field, getattr(rec, field) + duration)
        if name == COMPILE_EVENT:
            rec.cache_hit = getattr(_LOCAL, "cache_hit", 0.0)
            _LOCAL.cache_hit = 0.0
            del _OPEN[key]
            _DONE[key] = (_DONE.get(key, (0, None))[0] + 1, rec)


def install_listeners() -> bool:
    """Register the process-wide listener once. Returns whether monitoring
    is observable on this jax (callers report honestly when it is not)."""
    global _INSTALLED
    with _LOCK:
        if _INSTALLED:
            return True
        import jax

        mon = getattr(jax, "monitoring", None)
        if mon is None or not hasattr(
                mon, "register_event_duration_secs_listener"):
            return False
        if hasattr(mon, "register_scalar_listener"):
            mon.register_scalar_listener(_on_scalar)
        mon.register_event_listener(_on_event)
        mon.register_event_duration_secs_listener(_on_duration)
        _INSTALLED = True
        return True


class CompileFence:
    """Per-run trace + compile counters (see module docstring)."""

    def __init__(self):
        #: traces per program name — the ``DecodeEngine.trace_counts`` twin
        self.trace_counts: dict[str, int] = {}
        #: executables compiled or loaded while started, one each
        self.compile_events = 0
        #: seconds of top-level compile phases while started
        self.compile_s = 0.0
        #: False when jax.monitoring cannot be observed on this jax —
        #: compile_events==0 then means "unobservable", not "no compiles"
        self.monitoring_available = False

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        self.monitoring_available = install_listeners()
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

    def _duration(self, name: str, duration: float, top: bool) -> None:
        if name == COMPILE_EVENT:
            self.compile_events += 1
        if top:
            self.compile_s += duration

    def snapshot(self) -> tuple[dict, int]:
        """(trace_counts copy, compile event count) — the steady-state
        fence idiom: snapshot after the warm lap, assert flat later."""
        return dict(self.trace_counts), self.compile_events
