"""Host spans of the generator, written into JAX's profiler trace.

Every layer boundary of the host path opens a :func:`span`: the front
door (``repro.plan``, ``repro.generate``), program construction and
dispatch (``repro.build``, ``repro.dispatch``), waits on the device
(``repro.wait``) and the host sink (``repro.sink.*``). A span is a
``jax.profiler.TraceAnnotation``, so it lands in the profiler's own
trace, on the clock the device planes use, and an idle stretch of the
chip can be put down to the step the host was in. With no profiler
running a span records nothing and costs about a microsecond; there is
no buffer, exporter or switch of our own.

:func:`graph` (entered once per ``api.generate``) gives every span of one
request the same ``graph`` argument, a per-process counter.

Open spans in host code only: inside a jitted or ``shard_map``-ped
function a span would time the tracing, once, not the running. Every
name starts with ``repro.``, apart from the names callers give their own
spans (a benchmark's ``window``, ``graph``, ``plan``, ``generate``).
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
from typing import Iterator

import jax

_GRAPH: contextvars.ContextVar = contextvars.ContextVar("repro_graph",
                                                        default=None)
_GRAPH_IDS = itertools.count(1)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` with ``args`` (ints or short strings) and the
    current graph id, to use as a context manager. ``set_metadata(**kw)``
    on the entered span adds arguments known only at its end."""
    graph_id = _GRAPH.get()
    if graph_id is not None:
        args["graph"] = graph_id
    return jax.profiler.TraceAnnotation(name, **args)


@contextlib.contextmanager
def graph() -> Iterator[int]:
    """Give the spans opened inside the next graph id; yields the id."""
    graph_id = next(_GRAPH_IDS)
    token = _GRAPH.set(graph_id)
    try:
        yield graph_id
    finally:
        _GRAPH.reset(token)
