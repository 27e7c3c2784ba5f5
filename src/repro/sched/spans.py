"""Spans the scheduler records where its work happens (DESIGN.md §13).

``span(name, **args)`` is a context manager that does two things:

  1. it opens ``jax.profiler.TraceAnnotation(name, **args)``, so under a
     running profiler the span lands in the trace beside the device's
     operations;
  2. inside a release it appends one :class:`Span` to that release's
     bounded buffer (``JobStats.spans``).

The current release is the innermost ``release(...)`` span open on the
calling thread: ``RTJob._run`` opens one per iteration, and every span
opened below it on that thread records its parent and the release's
identifier, so engine code needs no handle on the job.  A span opened
outside any release (profiling before admission, a test driving the
executor by hand) records nothing and raises nothing; it still reads the
clock, so a counter that shares its reading keeps working.

Timestamps are ``time.monotonic_ns()``, the clock the job's schedule and
``time.monotonic()`` readers use.  The profiler's own host events sit on
another base (``jax.profiler.ProfileData`` gives them relative to the
trace's start), so a reader places a recorded span on a trace by the
offset between a span it knows on both clocks.

With no profiler running a span costs an annotation that does nothing,
two clock readings and one tuple append: no string is formatted and no
lock is taken.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import NamedTuple

from jax.profiler import TraceAnnotation

#: spans kept per job (oldest dropped first): a daemon that runs for days
#: holds a bounded tail; a 50 s benchmark window of the busiest job
#: records about 2,600
BUFFER = 8192

_ids = itertools.count(1)
_local = threading.local()


class Span(NamedTuple):
    """One finished span; times in ``time.monotonic_ns()``."""
    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: int          # 0 for a release's root span
    release: int            # the root span's id, shared by the release
    args: dict


def new_buffer() -> deque:
    return deque(maxlen=BUFFER)


class span:
    """``with span("sched.slice", i=3, op="decode") as s: ...``;
    ``s.seconds`` is the measured duration after the block."""

    __slots__ = ("name", "args", "start_ns", "end_ns", "_ann", "_id",
                 "_release", "_parent", "_buf")

    def __init__(self, name: str, **args):
        self.name, self.args = name, args
        self._buf = None

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        stack = getattr(_local, "stack", None)
        if self._buf is not None or stack:
            if self._buf is None:           # a child of the open span
                top = stack[-1]
                self._buf, self._release = top._buf, top._release
                self._parent = top._id
            self._id = next(_ids)
            if self._release == 0:          # a release's root
                self._release = self._id
            if stack is None:
                stack = _local.stack = []
            stack.append(self)
        self.start_ns = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.monotonic_ns()
        self._ann.__exit__(None, None, None)
        if self._buf is not None:
            _local.stack.pop()
            self._buf.append(Span(self.name, self.start_ns, self.end_ns,
                                  self._id, self._parent, self._release,
                                  self.args))
        return False

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def release(buffer: deque, **args) -> span:
    """The root span ``sched.release`` of one release, recorded into
    ``buffer``; the spans opened below it on this thread join it."""
    s = span("sched.release", **args)
    s._buf, s._release, s._parent = buffer, 0, 0
    return s
