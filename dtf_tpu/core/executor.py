"""Fenced AOT program executor — the one place compiled programs are born.

Every compiled program in the codebase (train step, eval step, the serve
tier's prefill / decode-verify / draft pair / page load-save) used to
hand-roll the same four-part idiom: a trace-counting wrapper (the
recompile fence), a ``jax.jit`` with pinned ``out_shardings`` (so AOT
executables reject resharded inputs instead of silently re-laying-out),
version-gated donation, and a hand-written analysis "step view" twin so
the comms/memory budget fences cover the exact graph that serves. Ten
copies of that idiom had ten chances to drift.

:func:`program` is now the choke point. It returns a :class:`Program`
that owns all four concerns:

- **fence** — ``counts[name]`` increments once per TRACE (not per call),
  into whatever dict the caller shares (``DecodeEngine.trace_counts``,
  the telemetry ``CompileFence``); any post-steady-state increment is a
  shape-driven retrace and the owning test fails.
- **pins** — ``jit_kw`` carries ``in_shardings``/``out_shardings``
  verbatim; the executor adds nothing and removes nothing, so a
  program's compiled layout contract is exactly what its builder wrote.
- **donation** — ``donate=`` donates ``donate_args`` (the state, by
  default); the analyzer's memory pass checks every donated leaf is
  aliased to an output (``dropped-donation``).
- **step view** — ``abstract_args`` + ``arg_shardings`` register what
  the analysis registry needs: :meth:`Program.lower` with no arguments
  lowers against the registered abstracts, and
  ``dtf_tpu.analysis.configs.StepView.of`` reads ``arg_shardings`` for
  the resident-state memory model. Analysis step views enumerate a
  builder's program table instead of re-spelling its jit kwargs.

The srclint AOT fence (``raw-aot-compile``) makes this structural: raw
``.lower(``/``.compile(`` idioms outside this module (+ tune/ + tests)
are findings unless pinned with ``# aot-ok: <why>``.

Importing this module installs the compile fence's process-wide
``jax.monitoring`` listener (:mod:`dtf_tpu.telemetry.fence`), so every
program born here has a compile record — its trace, lower and
compile-or-load seconds — under :attr:`Program.trace_name`, whether or not
a ``Telemetry`` exists yet when it compiles.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, MutableMapping, Optional

import jax

from dtf_tpu.telemetry import fence as _fence

PyTree = Any

_fence.install_listeners()


def fenced(name: str, body: Callable, counts: Optional[MutableMapping]):
    """Wrap ``body`` so each TRACE bumps ``counts[name]`` (no-op wrapper
    when ``counts`` is None). The wrapped body runs once per trace under
    ``jax.jit``, so a steady-state count above the compile-time value is
    a retrace — the fence every engine/trainer test pins."""
    if counts is None:
        return body
    counts.setdefault(name, 0)

    @functools.wraps(body)
    def wrapped(*args, **kwargs):
        counts[name] += 1
        return body(*args, **kwargs)

    return wrapped


def donation_argnums(donate: bool, argnums: tuple = (0,)) -> tuple:
    """The donation decision for a program: ``argnums`` when the caller
    asked, else ``()``."""
    return tuple(argnums) if donate else ()


class Program:
    """A fenced program: the jitted callable plus its registration.

    Dispatch (``__call__``) and every jit-surface attribute (``trace``,
    ``eval_shape``, ...) delegate to the wrapped jit, so a Program is a
    drop-in for the raw ``jax.jit`` object it replaces. On top of that:

    - ``body`` — the unfenced python body, for analysis views that
      compose two programs into one lowered step;
    - ``abstract_args`` — the registered operand abstracts;
      :meth:`lower`/:meth:`aot` with no arguments use them;
    - ``arg_shardings`` — the declared input layouts the analysis
      memory pass prices (None = the abstract leaves carry their own);
    - ``compiled`` — the AOT executable after :meth:`aot` (None before);
    - ``trace_name`` — the name JAX traces the fenced body under (the
      program is ``jit_<trace_name>`` on the device): the key of its
      compile record, :func:`dtf_tpu.telemetry.fence.program_compile`.
    """

    def __init__(self, name: str, jitted: Callable, body: Callable, *,
                 abstract_args: Optional[tuple] = None,
                 arg_shardings: Any = None):
        self.name = name
        self.jitted = jitted
        self.body = body
        self.abstract_args = abstract_args
        self.arg_shardings = arg_shardings
        self.compiled = None
        # jax.jit names what it traces after the function (fenced's
        # wrapper carries its body's name)
        self.trace_name = getattr(body, "__name__", name)

    def __call__(self, *args, **kwargs):
        return self.jitted(*args, **kwargs)

    def __getattr__(self, attr):
        # only reached for attributes not set in __init__ — the jit API
        # surface (trace, eval_shape, clear_cache, ...)
        return getattr(self.jitted, attr)

    def __repr__(self):
        return f"Program({self.name!r})"

    def lower(self, *args, **kwargs):
        """Lower against explicit operands, or the registered
        ``abstract_args`` when called bare."""
        if not args and not kwargs:
            if self.abstract_args is None:
                raise ValueError(
                    f"program {self.name!r} has no registered "
                    f"abstract_args; pass operands to lower()")
            args = self.abstract_args
        return self.jitted.lower(*args, **kwargs)

    def aot(self, *args, **kwargs):
        """lower→compile (the AOT idiom): returns the executable, which
        rejects resharded/reshaped operands instead of retracing. Also
        stored as ``self.compiled``. Traces the fenced body exactly
        once."""
        self.compiled = self.lower(*args, **kwargs).compile()
        return self.compiled


def program(name: str, body: Callable, *,
            counts: Optional[MutableMapping] = None,
            jit_kw: Optional[dict] = None,
            donate: Optional[bool] = None,
            donate_args: tuple = (0,),
            abstract_args: Optional[tuple] = None,
            arg_shardings: Any = None,
            table: Optional[MutableMapping] = None) -> Program:
    """Build a fenced :class:`Program` — the only sanctioned spelling of
    ``jax.jit(counted(fn), **pins)[.lower().compile()]``.

    ``jit_kw`` is passed to ``jax.jit`` verbatim (in/out sharding pins,
    static argnums). ``donate=None`` means the program has no donation
    decision (serve programs); a bool routes through
    :func:`donation_argnums`. ``table`` registers the program under
    ``name`` in the caller's program table.
    """
    kw = dict(jit_kw or {})
    if donate is not None:
        kw["donate_argnums"] = donation_argnums(donate, donate_args)
    prog = Program(name, jax.jit(fenced(name, body, counts), **kw), body,
                   abstract_args=abstract_args, arg_shardings=arg_shardings)
    if table is not None:
        table[name] = prog
    return prog
