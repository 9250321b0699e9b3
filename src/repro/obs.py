"""The program's own instrumentation: counters, and host spans on the
profiler's clock.

**Counters** are always on. Each is a name in one registry: ``count`` adds
to it, ``counted`` reads it, summed over the counters under it
(``counted("dispatch")`` is ``dispatch.solver + dispatch.engine +
dispatch.fused``). The layers count compiled-program launches
(``dispatch.<layer>``, one per call: each is a host boundary crossing) and
(re)traces of their jitted bodies (``trace.<layer>``, bumped inside the
traced body, so it fires at compile time only).

**Spans** are recorded only while a profiler session records host events
(``jax.profiler.TraceAnnotation.is_enabled()``). Then ``span(name,
**attrs)`` records its name, its parent (the span open on the same thread
when it began), its start and end (``time.perf_counter_ns``) and a small
dict of counts set at the same boundary, and opens a ``TraceAnnotation`` of
the same name, so the span lands on the trace's host plane beside the
device's operations. Off, ``span`` returns the shared no-op ``OFF``, which
is falsy (``if sp:`` skips work only a record needs), records nothing and
never waits for the device. Records cover the current or the last session:
they are cleared the first time a span sees the profiler on after one saw
it off. While a session records, each cyclic garbage collection is a
``host.gc`` span with its ``generation``; that hook is installed at the
first recorded span, so a process that is never traced installs none.
"""
from __future__ import annotations

import gc
import threading
import time
from collections import Counter
from typing import Optional

_COUNTS: Counter = Counter()


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    _COUNTS[name] += n


def counted(name: str) -> int:
    """The counter ``name`` plus every counter under it (``name.*``)."""
    under = name + "."
    return sum(v for k, v in _COUNTS.items()
               if k == name or k.startswith(under))


class _Off:
    """The span returned while no session records: does nothing."""
    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None


OFF = _Off()


class Span:
    """One recorded span. ``attrs`` holds the counts set at its boundary;
    ``parent`` is the enclosing span on the same thread, or None."""
    __slots__ = ("name", "attrs", "parent", "start_ns", "end_ns", "_ann")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs
        self.parent: Optional[Span] = None
        self.start_ns = self.end_ns = 0

    def __bool__(self) -> bool:
        return True

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def __enter__(self) -> "Span":
        stack = _stack()
        self.parent = stack[-1] if stack else None
        stack.append(self)
        _RECORDS.append(self)
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._ann = None
        _stack().pop()


_RECORDS: list[Span] = []
_LOCAL = threading.local()
_seen_off = True            # a span saw the profiler off since the last on
_gc_hooked = False
_GC_OPEN: list = []         # the collection in progress, while recorded


def _stack() -> list:
    s = getattr(_LOCAL, "stack", None)
    if s is None:
        s = _LOCAL.stack = []
    return s


def _annotation(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def _profiler_on() -> bool:
    """``TraceAnnotation.is_enabled``, resolved at the first call so that
    importing this module imports no jax."""
    global _profiler_on
    from jax.profiler import TraceAnnotation
    _profiler_on = TraceAnnotation.is_enabled
    return _profiler_on()


def _recording() -> bool:
    """True while a session records; starts a fresh set of records at the
    first look after the profiler was seen off."""
    global _seen_off, _gc_hooked
    if not _profiler_on():
        _seen_off = True
        return False
    if _seen_off:
        _seen_off = False
        _RECORDS.clear()
        if not _gc_hooked:
            _gc_hooked = True
            gc.callbacks.append(_on_gc)
    return True


def span(name: str, **attrs):
    """A context manager: a recorded ``Span`` while a profiler session
    records host events, else ``OFF``."""
    if not _recording():
        return OFF
    return Span(name, attrs)


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        if _recording():
            sp = Span("host.gc", {"generation": info["generation"]})
            sp.__enter__()
            _GC_OPEN.append(sp)
    elif _GC_OPEN:
        _GC_OPEN.pop().__exit__(None, None, None)


def spans(name: Optional[str] = None) -> list[Span]:
    """The recorded spans of the current or last session, in the order they
    began: those named ``name``, or all."""
    if name is None:
        return list(_RECORDS)
    return [s for s in _RECORDS if s.name == name]
