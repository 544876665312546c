"""Where JAX keeps its persistent compilation cache, and what compiles.

One place decides it for every entry point that compiles for the chip
(``chip_smoke.py`` and the benchmark CLIs).  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set in code; otherwise the cache lives at one fixed directory inside the
checkout, ``<repo>/.jax_cache`` (git-ignored).  The directory is part of
what a later process must find again, so it never derives from a temp
name, a pid or the clock.

:class:`CompileCounter` counts the backend compilations of each jitted
function into an observability bundle's registry while it is attached.
"""

from __future__ import annotations

import os
import threading

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set and non-empty, else the
    fixed in-checkout directory."""
    return os.environ.get(ENV_VAR) or CHECKOUT_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`compile_cache_dir` and return
    that directory.  Call before the first compilation."""
    path = compile_cache_dir()
    if path == CHECKOUT_DIR:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


#: JAX's monitoring events: one around every backend compilation (start as
#: a scalar, end as a time span, both with ``fun_name``), a load from the
#: persistent cache included, and one for each such load
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts each backend compilation into ``obs.metrics`` as the counter
    ``jax.compiles.<fun_name>`` (not deterministic: it depends on what the
    process compiled before) and, when ``obs.tracer`` annotates the
    profiler's trace, marks it there as ``compile:<fun_name>``.  A program
    loaded from the persistent cache is not counted.

    Counts every compilation in the process from construction until
    :meth:`close`, on whichever thread compiles.
    """

    def __init__(self, obs):
        self._obs = obs
        self._local = threading.local()
        jax.monitoring.register_scalar_listener(self._on_start)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_time_span_listener(self._on_end)

    def close(self) -> None:
        jax.monitoring.unregister_scalar_listener(self._on_start)
        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_time_span_listener(self._on_end)

    def _open(self) -> list:
        """This thread's compilations in flight: ``[fun_name, cache_hit,
        annotation]`` each, innermost last."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _on_start(self, event: str, value: float, **kw) -> None:
        if event != COMPILE_EVENT:
            return
        name = kw.get("fun_name", "?")
        annotation = getattr(self._obs.tracer, "annotation", None)  # NullTracer has none
        ann = annotation("compile:" + name) if annotation is not None else None
        if ann is not None:
            ann.__enter__()
        self._open().append([name, False, ann])

    def _on_event(self, event: str, **kw) -> None:
        stack = self._open()
        if event == CACHE_HIT_EVENT and stack:
            stack[-1][1] = True

    def _on_end(self, event: str, start: float, end: float, **kw) -> None:
        stack = self._open()
        if event != COMPILE_EVENT or not stack:
            return
        fun_name, cache_hit, ann = stack.pop()
        if ann is not None:
            ann.__exit__(None, None, None)
        if not cache_hit:
            self._obs.metrics.counter("jax.compiles." + fun_name, deterministic=False).inc()
