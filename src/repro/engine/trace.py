"""Host spans of the serving path, on the profiler's own clock.

A span is a ``jax.profiler.TraceAnnotation``. With no profiler running it
costs one TraceMe enter and exit; under ``jax.profiler.trace`` (or
``start_trace``) it lands on the trace's host plane beside the device ops,
so a device idle gap can be named by the host work it fell in. Spans are
per step or per dispatch, never per sequence or per token, and their names
are constants: nothing is formatted on the serving path.

``SPANS`` lists every name the program emits, outermost first:

- ``je.step`` / ``je.submit``: ``ServingJobEngine.step`` and ``submit``
  (Algorithm 1 placement);
- ``te.step``: ``FlowServe.step``; inside it ``te.plan`` (prefix resolve,
  prefetch pump, ``prepare_next``), ``te.prefill`` (packing, dispatch and
  commit of the step's prefill) with ``te.prefill.fetch`` (its first-token
  fetch), and ``te.decode.sync`` / ``te.decode.dispatch`` /
  ``te.decode.fetch`` (batch-state sync, decode dispatch, token fetch);
- ``distflow.transfer``: a KV run leaving in ``migrate_out`` or landing in
  the destination's import.
"""
from __future__ import annotations

import functools

from jax.profiler import TraceAnnotation

SPANS = ("je.step", "je.submit", "te.step", "te.plan", "te.prefill",
         "te.prefill.fetch", "te.decode.sync", "te.decode.dispatch",
         "te.decode.fetch", "distflow.transfer")

span = TraceAnnotation


def spanned(name: str):
    """Decorator: the call runs inside span ``name``."""
    if name not in SPANS:
        raise ValueError(f"{name!r} is not in trace.SPANS")

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with TraceAnnotation(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
