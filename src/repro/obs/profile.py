"""Host spans on the profiler's clock (DESIGN.md §17).

The serving path names its phases and dispatch sites with
``jax.profiler.TraceAnnotation`` spans. Inside a profiler session
(``jax.profiler.start_trace`` .. ``stop_trace``) each span lands in the same
XSpace as the device ops, on the same clock, and its counters travel as span
arguments (``ProfileEvent.stats`` when the trace is read back). Outside a
session :func:`span` returns one shared null context after a single
``TraceAnnotation.is_enabled()`` check, so the serving path does no other
work and its outputs are bit-identical either way.

Spans never block: a dispatch's span covers the host's dispatch, and the
device work it launched appears on the device planes. Wall time stays out
of the deterministic step-clock log (obs/recorder.py).
"""

from __future__ import annotations

import functools

from jax.profiler import TraceAnnotation


class _NullSpan:
    """What :func:`span` returns outside a profiler session."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats) -> None:
        pass


_NULL = _NullSpan()


def enabled() -> bool:
    """True while a profiler session is recording host spans."""
    return TraceAnnotation.is_enabled()


def span(name: str, **stats):
    """A context manager that records ``name`` with ``stats`` as its
    arguments while a profiler session runs, and does nothing otherwise.

    A stat given as a zero-argument callable is computed only inside a
    session: pass one where a counter costs work (a pass over a page
    table). Counters known only once the span's work is done are added
    with ``set_metadata(**stats)`` on the entered span.
    """
    if not TraceAnnotation.is_enabled():
        return _NULL
    return TraceAnnotation(
        name, **{k: v() if callable(v) else v for k, v in stats.items()}
    )


def call(name: str, fn, *args, **kwargs):
    """Dispatch ``fn(*args, **kwargs)`` inside ``span(name)``; never blocks."""
    with span(name):
        return fn(*args, **kwargs)


def spanned(name: str):
    """Decorator: every call of the function runs inside ``span(name)``."""

    def wrap(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return wrapped

    return wrap
