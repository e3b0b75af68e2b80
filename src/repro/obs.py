"""The program's own tracing: device phase scopes, host spans and
trace-time counters, under names that a profiler trace and the
benchmark's readers find after any refactor.

* ``scope(phase)`` is ``jax.named_scope('repro.<phase>')`` around the
  ops of one phase of a round (``PHASES``).  It is op metadata only: it
  adds no op and leaves the compiled arithmetic as it is.  In a profiler
  trace every device op carries its name stack, so each op's time goes
  to the innermost ``repro.`` phase in it; an op with none is unscoped.
  Scopes reach only ops traced inside them, so code run eagerly between
  segments carries none.
* ``span(name)`` is a host span, ``jax.profiler.TraceAnnotation(
  'repro.<name>')``, on the device trace's clock.  Inside
  ``recording()`` it is also kept in memory as a ``Span``; outside, it
  keeps nothing.
* ``count(name, **numbers)`` records what a kernel wrapper computes
  from its static shapes while it is traced (never while it runs);
  ``counters()`` reads the latest trace of each wrapper.

Capture a trace with the program's spans in it::

    with jax.profiler.trace(log_dir), obs.recording() as spans:
        runner.run()
"""
from __future__ import annotations

import contextlib
import time
from typing import NamedTuple, Optional

import jax

PREFIX = 'repro.'
#: device phases of a round: models moved between the state and
#: training, local training, the simulated wire outside the aggregation
#: kernel, Eq. 6-8, and evaluation
PHASES = ('rows', 'train', 'wire', 'aggregate', 'eval')


class Span(NamedTuple):
    name: str
    parent: Optional[str]       # the recorded span open around it
    start: float                # time.perf_counter() seconds
    end: float


class _Recording:
    def __init__(self):
        self.spans = []
        self.open = []


_recording: Optional[_Recording] = None
_counters: dict = {}


def scope(phase: str):
    """The device phase scope ``repro.<phase>``."""
    if phase not in PHASES:
        raise ValueError(f'unknown phase {phase!r} (want one of {PHASES})')
    return jax.named_scope(PREFIX + phase)


class span:
    """Host span ``repro.<name>``: a profiler annotation, kept in memory
    with its parent while ``recording()`` is open."""

    def __init__(self, name: str):
        self.name = name
        self._annotation = jax.profiler.TraceAnnotation(PREFIX + name)
        self._rec = self._start = None

    def __enter__(self):
        self._annotation.__enter__()
        self._rec = _recording
        if self._rec is not None:
            self._rec.open.append(self.name)
            self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        rec = self._rec
        if rec is not None:
            end = time.perf_counter()
            rec.open.pop()
            rec.spans.append(Span(self.name, rec.open[-1] if rec.open
                                  else None, self._start, end))
        self._annotation.__exit__(*exc)


@contextlib.contextmanager
def recording():
    """Keep the spans closed inside in memory; yields their list, in the
    order they close."""
    global _recording
    outer, _recording = _recording, _Recording()
    try:
        yield _recording.spans
    finally:
        _recording = outer


def self_seconds(spans) -> dict:
    """Seconds of each span name less those of the spans directly inside
    it, summed over the name's spans."""
    out = {}
    for s in spans:
        out[s.name] = out.get(s.name, 0.0) + (s.end - s.start)
        if s.parent is not None:
            out[s.parent] = out.get(s.parent, 0.0) - (s.end - s.start)
    return out


def count(name: str, **numbers: int) -> None:
    """Record ``numbers`` for wrapper ``name`` (the latest trace wins)."""
    _counters[name] = dict(numbers)


def counters() -> dict:
    """Wrapper name -> the numbers its latest trace recorded."""
    return {name: dict(nums) for name, nums in _counters.items()}
