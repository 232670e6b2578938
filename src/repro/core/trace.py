"""The program's spans and counters, on the profiler's clock.

    from repro.core import trace

    with trace.span("engine.step"):
        ...

A span names the work between two layer boundaries (``engine.*``,
``sched.*``, ``trainer.*``).  While no recorder is on and no
``jax.profiler`` trace runs, ``span`` returns one shared null context:
nothing is allocated, no clock is read, nothing is recorded.

* While a ``jax.profiler`` trace runs, each span is written into it as a
  ``jax.profiler.TraceAnnotation`` of the same name, so the host's spans
  and the device's events share one clock.
* Between ``enable()`` and ``disable()`` each span is also kept in memory
  as a :class:`Span` (name, start and end from ``time.perf_counter_ns``,
  index of the enclosing span), with two counters: backend compiles and
  their seconds under the innermost open span (a ``jax.monitoring``
  listener, registered on the first ``enable()``), and per-uid trajectory
  stamps at the buffer's state changes (:func:`stamp`).  ``take()`` hands
  over what was recorded since the last call and clears it.

Spans are those of one thread.  The module never writes a file, and it
imports jax only once jax is loaded or a recorder is enabled, so the
jax-free modules (``core/buffer.py``) can stamp.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import Dict, List, NamedTuple, Optional, Sequence

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: int          # index of the enclosing span in the same list; -1


@dataclasses.dataclass
class Taken:
    """What a recorder held at ``take()``: spans in order of entry;
    compiles by the innermost span open at the time (``""`` for none) as
    [count, seconds]; and per uid, the first ``perf_counter_ns`` of each
    stamped state change."""
    spans: List[Span]
    compiles: Dict[str, List[float]]
    stamps: Dict[int, Dict[str, int]]


class _Null:
    __slots__ = ()

    def __enter__(self):
        pass

    def __exit__(self, typ, value, tb):
        pass


_NULL = _Null()


class Recorder:
    """One ``enable()`` .. ``disable()``.  ``total`` and ``count`` are the
    seconds and entries of each span name since ``enable()``, the view the
    benchmark's own host spans give; ``take`` clears the rest."""

    def __init__(self):
        self.total: Dict[str, float] = {}
        self.count: Dict[str, int] = {}
        # a slot per span in order of entry, filled with a plain tuple of
        # Span's fields at its exit (tuples of numbers and a str are
        # left alone by the cyclic garbage collector)
        self._spans: List[Optional[tuple]] = []
        self._open: List[_Recorded] = []
        self._compiles: Dict[str, List[float]] = {}
        self._stamps: Dict[int, Dict[str, int]] = {}

    def take(self) -> Taken:
        if self._open:
            raise RuntimeError("trace: take() inside an open span "
                               f"({self._open[-1].name!r})")
        out = Taken([Span(*s) for s in self._spans], self._compiles,
                    self._stamps)
        self._spans, self._compiles, self._stamps = [], {}, {}
        return out


class _Recorded:
    __slots__ = ("rec", "name", "ann", "i", "parent", "start")

    def __init__(self, rec: Recorder, name: str):
        self.rec, self.name = rec, name
        self.ann = _annotation(name) if _tracing() else None

    def __enter__(self):
        rec = self.rec
        if self.ann is not None:
            self.ann.__enter__()
        self.i = len(rec._spans)
        self.parent = rec._open[-1].i if rec._open else -1
        rec._spans.append(None)
        rec._open.append(self)
        self.start = time.perf_counter_ns()

    def __exit__(self, typ, value, tb):
        t = time.perf_counter_ns()
        rec, name = self.rec, self.name
        rec._spans[self.i] = (name, self.start, t, self.parent)
        rec._open.pop()
        rec.total[name] = rec.total.get(name, 0.0) + (t - self.start) * 1e-9
        rec.count[name] = rec.count.get(name, 0) + 1
        if self.ann is not None:
            self.ann.__exit__(typ, value, tb)


_rec: Optional[Recorder] = None
_is_enabled = None           # TraceAnnotation.is_enabled, once jax is loaded
_listening = False


def _tracing() -> bool:
    """Whether a jax.profiler trace is running (none can without jax)."""
    global _is_enabled
    if _is_enabled is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation
        _is_enabled = TraceAnnotation.is_enabled
    return _is_enabled()


def _annotation(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def span(name: str):
    """Context manager over the work named ``name`` (see the module)."""
    if _rec is not None:
        return _Recorded(_rec, name)
    if (_is_enabled or _tracing)():
        return _annotation(name)
    return _NULL


def stamp(uid: int, event: str) -> None:
    """Record the first time ``event`` happens to trajectory ``uid``
    (``running``, ``harvest``, ``done``, ``consume``); only while on."""
    rec = _rec
    if rec is not None:
        rec._stamps.setdefault(uid, {}).setdefault(event,
                                                   time.perf_counter_ns())


def _on_duration(event: str, duration: float, **_) -> None:
    rec = _rec
    if rec is None or event != COMPILE_EVENT:
        return
    name = rec._open[-1].name if rec._open else ""
    c = rec._compiles.setdefault(name, [0, 0.0])
    c[0] += 1
    c[1] += duration


def enable() -> Recorder:
    """Start a fresh recorder and return it."""
    global _rec, _listening
    if not _listening:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    _rec = Recorder()
    return _rec


def disable() -> None:
    global _rec
    _rec = None


def take() -> Taken:
    """What the enabled recorder held since the last call (cleared)."""
    if _rec is None:
        raise RuntimeError("trace: take() with no recorder enabled")
    return _rec.take()


def self_ns(spans: Sequence[Span]) -> List[int]:
    """Each span's duration less the part its child spans cover."""
    out = [s.end_ns - s.start_ns for s in spans]
    for s in spans:
        if s.parent >= 0:
            out[s.parent] -= s.end_ns - s.start_ns
    return out


def between(stamps: Dict[int, Dict[str, int]], first: str,
            then: str) -> Dict[int, float]:
    """Seconds from stamp ``first`` to stamp ``then`` of each trajectory
    stamped with both (``done`` to ``consume``: how long a finished
    trajectory waited for an update)."""
    return {u: (s[then] - s[first]) * 1e-9 for u, s in stamps.items()
            if first in s and then in s}
