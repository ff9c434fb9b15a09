"""Program spans on the profiler's clock.

:func:`span` writes a ``jax.profiler.TraceAnnotation``.  While a profiler
session runs (``jax.profiler.trace`` or ``start_trace``), the span lands in
the trace's host plane beside the device planes, on the same clock, with
its name, start, end, its parent (by nesting on the thread) and the
attributes given; with no session it records nothing and costs its Python
call.

The served path opens one span at each layer boundary, and every span's
name starts with its layer: ``server.`` (``Server``'s fronts, step and
finalize), ``engine.`` (``DecodeEngine``), ``graph.`` (``CommandGraph``
launch and capture), ``batch.`` (batch formation) and ``dispatch.`` (lane
pick and launch).  Attributes known only inside the span are added
through the annotation's ``set_metadata``::

    with span("graph.launch", graph=g.name) as sp:
        ...
        sp.set_metadata(first=1)
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **attrs) -> TraceAnnotation:
    """A profiler span named ``name`` carrying ``attrs``; use it as a
    context manager around the work it times."""
    return TraceAnnotation(name, **attrs)
