"""Spans and counters of the serving path, recorded while a JAX profiler
session is on.

The switch is the profiler itself: `on()` is
`jax.profiler.TraceAnnotation.is_enabled()`, true only between
`jax.profiler.start_trace` and `stop_trace`. With no session a boundary of
the serving path costs that one check; there is no flag or option.

A span is a name, `start_ns` and `end_ns` on `time.monotonic_ns()` (never
a clock injected into the program, so spans line up with a device trace
whatever clock the program was given), the request id it serves (`rid`,
or a tuple of them for a batched step), the id of the span that caused it
(`parent`), the thread it ran on and a few named values (`args`).

Spans go to two sinks: a `TraceAnnotation` of the same name and rid, so the
profiler's host plane shows them beside the device ops, and a bounded
in-memory buffer that in-process readers query with `spans()` (a full
buffer drops the oldest span and counts it in `dropped`). Counters are
integers bumped under the same switch.

Hot boundaries test the switch themselves and stamp only when it is on::

    sp = TRACER.begin("decode.step", rid=rids) if on() else None
    ...
    if sp is not None:
        TRACER.end(sp, rows=n)

and code off the hot path may use `with TRACER.span(name, ...)`. A span
whose start was stamped on another thread (a queue wait, a handoff) is
added whole with `record`.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

import jax
from jax.profiler import TraceAnnotation

on = TraceAnnotation.is_enabled
now_ns = time.monotonic_ns

Rid = Union[None, int, Tuple[int, ...]]


@dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int = 0
    rid: Rid = None
    parent: Optional[int] = None
    thread: str = ""
    args: Dict[str, object] = field(default_factory=dict)
    id: int = 0

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


class _Noop:
    """What `span` returns with no profiler session: enters and exits,
    holds nothing."""
    id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def set(self, **args) -> None:
        pass


NOOP = _Noop()


class _Open:
    """A span being timed: `Tracer.begin` made it, `Tracer.end` closes it."""
    __slots__ = ("span", "annotation", "tracer")

    def __init__(self, tracer: "Tracer", span: Span, annotation):
        self.tracer, self.span, self.annotation = tracer, span, annotation

    @property
    def id(self) -> int:
        return self.span.id

    def set(self, **args) -> None:
        self.span.args.update(args)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.tracer.end(self)
        return None


class Tracer:
    def __init__(self, capacity: int = 1 << 18):
        self._buf: deque = deque(maxlen=capacity)
        self._counters: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._open = threading.local()      # this thread's open span ids
        self.dropped = 0

    def new_id(self) -> int:
        """An id for a span recorded later (a request's, known at submit)."""
        return next(self._ids)

    def span(self, name: str, rid: Rid = None, parent: Optional[int] = None,
             **args):
        """Context manager timing its body; a shared no-op without a
        profiler session."""
        if not on():
            return NOOP
        return self.begin(name, rid, parent, **args)

    def begin(self, name: str, rid: Rid = None, parent: Optional[int] = None,
              **args) -> _Open:
        """Open a span now; the caller has checked `on()`."""
        ann = TraceAnnotation(name, rid=str(rid))
        ann.__enter__()
        sp = Span(name, now_ns(), rid=rid, parent=parent,
                  thread=threading.current_thread().name, args=args,
                  id=next(self._ids))
        stack = getattr(self._open, "ids", None)
        if stack is None:
            stack = self._open.ids = []
        stack.append(sp.id)
        return _Open(self, sp, ann)

    def end(self, op: _Open, **args) -> int:
        sp = op.span
        sp.end_ns = now_ns()
        op.annotation.__exit__(None, None, None)
        self._open.ids.remove(sp.id)
        if args:
            sp.args.update(args)
        self._keep(sp)
        return sp.id

    def record(self, name: str, start_ns: int, end_ns: int, rid: Rid = None,
               parent: Optional[int] = None, span_id: Optional[int] = None,
               **args) -> int:
        """Add a span stamped elsewhere, ending about now; the caller has
        checked `on()`. Its annotation marks the end, carrying both stamps."""
        with TraceAnnotation(name, rid=str(rid), start_ns=start_ns,
                             end_ns=end_ns):
            pass
        sp = Span(name, start_ns, end_ns, rid=rid, parent=parent,
                  thread=threading.current_thread().name, args=args,
                  id=next(self._ids) if span_id is None else span_id)
        self._keep(sp)
        return sp.id

    def current(self) -> Optional[int]:
        """Id of the innermost span open on this thread, or None."""
        stack = getattr(self._open, "ids", None)
        return stack[-1] if stack else None

    def count(self, name: str, n: int = 1) -> None:
        if not on():
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def _keep(self, sp: Span) -> None:
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(sp)

    # ---------------------------------------------------------- readers
    def spans(self, start_ns: Optional[int] = None,
              end_ns: Optional[int] = None) -> List[Span]:
        """Kept spans that overlap [start_ns, end_ns], in the order kept."""
        with self._lock:
            out = list(self._buf)
        lo = -1 << 63 if start_ns is None else start_ns
        hi = 1 << 63 if end_ns is None else end_ns
        return [s for s in out if s.end_ns >= lo and s.start_ns <= hi]

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._counters.clear()
            self.dropped = 0


TRACER = Tracer()


def rids(requests) -> Rid:
    """The rid a span over these requests carries: one rid, or a tuple."""
    if len(requests) == 1:
        return requests[0].rid
    return tuple(r.rid for r in requests)


def jit_named(name: str, fn, **jit_kwargs):
    """`jax.jit` of `fn` under `name`: the device trace's program line then
    reads `jit_<name>` instead of the wrapped function's own name."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)
