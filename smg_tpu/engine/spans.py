"""The engine's own host spans on the profiler's clock.

``jax.profiler.TraceAnnotation`` puts a named interval on the calling
thread's line of a running ``jax.profiler`` trace (``/start_profile``), next
to the device's lines, so an idle gap of the device can be put down to the
piece of host work that covered it.  With no trace running an annotation
costs about half a microsecond, and the engine takes two or three steps a
second.

The names are a contract (PERF.md section 3 lists them with the metric each
is for; ``tests/test_engine_tracing.py`` holds them to this tuple).  Nothing
finer than these: no span per token, per request or per layer on the host.
"""

from __future__ import annotations

import functools

from jax.profiler import TraceAnnotation

#: ``smg.submit.lock_wait``/``smg.submit`` on the submitting thread; the
#: rest on the step thread, ``smg.step.*`` nested inside ``smg.step`` except
#: ``smg.step.callbacks``, which runs after the step released the engine lock
SPAN_NAMES = (
    "smg.submit.lock_wait", "smg.submit", "smg.step", "smg.step.consume",
    "smg.step.admit", "smg.step.launch", "smg.step.postprocess",
    "smg.step.callbacks",
)


def spanned(name: str, attrs=None):
    """Run the decorated function inside the span ``name``.  ``attrs`` maps
    the function's result (when not None) to the span's attributes; it is
    called only while a trace runs."""
    assert name in SPAN_NAMES, name

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with TraceAnnotation(name) as span:
                out = fn(*args, **kwargs)
                if attrs is not None and out is not None and span.is_enabled():
                    span.set_metadata(**attrs(out))
                return out

        return wrapper

    return deco
