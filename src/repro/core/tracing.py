"""Spans at the layer boundaries of a search.

Each span is a ``jax.profiler.TraceAnnotation``: while a profile is being
captured it lands in the same trace, on the same clock, as the device's
operations; otherwise it costs about a microsecond. The same interval is
also timed with ``time.perf_counter`` and kept on the span, and the program
reports THAT number as ``TaskResult.train_seconds`` / ``eval_seconds`` /
``convert_seconds`` — the trace and the results measure one interval.

The spans a search writes (README "Tracing a search"):

- ``repro.session.plan`` — the session's planning before a round's submit;
- ``repro.unit`` — one scheduled unit on an executor, from its conversion
  to its last score;
- ``repro.convert`` — a prepared-data build (cache hits write none);
- ``repro.train`` / ``repro.eval`` — one unit's training and scoring;
- ``repro.eval.metric`` — the host reduction of the unit's probabilities
  to scores.

``search`` (the session's id) and ``unit`` (the task id, negative for a
fused batch) are the join keys: a span given them passes them on to every
span opened inside it on the same thread, so an executor's spans join the
session's plan spans across threads. Capture with
``jax.profiler.trace(log_dir)`` around ``Session.results``; the profiler
keeps spans in memory and writes them when the capture stops.
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["JOIN_KEYS", "Span", "annotate", "span"]

#: metadata keys a span passes on to the spans opened inside it
JOIN_KEYS = ("search", "unit")

_TL = threading.local()


class Span:
    """One annotated interval; ``seconds`` is set when the block exits."""

    __slots__ = ("name", "meta", "seconds", "_annotation", "_outer",
                 "_outer_span", "_t0")

    def __init__(self, name: str, meta: dict):
        self.name = name
        self.meta = meta
        self.seconds = 0.0

    def set(self, **meta) -> None:
        """Add metadata known only inside the block (a build's bytes)."""
        self._annotation.set_metadata(**meta)

    def __enter__(self) -> "Span":
        self._outer = getattr(_TL, "join", {})
        join = {**self._outer,
                **{k: self.meta[k] for k in JOIN_KEYS if k in self.meta}}
        _TL.join = join
        self._outer_span = getattr(_TL, "span", None)
        _TL.span = self
        self._annotation = TraceAnnotation(self.name, **{**join, **self.meta})
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        _TL.join = self._outer
        _TL.span = self._outer_span


def span(name: str, **meta) -> Span:
    """``with span("repro.train", family="gbdt", size=1) as sp: ...`` —
    afterwards ``sp.seconds`` holds the block's wall time."""
    return Span(name, meta)


def annotate(**meta) -> None:
    """Add metadata to the innermost span open on this thread, if any: what
    only the code inside knows, such as which rows a tree fit's levels read
    (``level_rows`` on ``repro.train``)."""
    sp = getattr(_TL, "span", None)
    if sp is not None:
        sp.set(**meta)
