"""The program's span recorder, on the profiler's clock.

``span(name, **attrs)`` marks one stage of work. It always enters a
``jax.profiler.TraceAnnotation`` of that name carrying the attrs, so
while a profiler session collects (``jax.profiler.start_trace`` ...
``stop_trace``, or a TensorBoard capture) every span shows in the
profiler's own trace, with its attrs as the event's stats, on the
device trace's clock, beside the device ops it waited for.

While a session collects, a span also appends a :class:`Record` to a
bounded in-memory buffer, timed with ``time.perf_counter_ns()`` (in the
written trace a span's start is that reading minus one constant, so
the two line up by a single offset). Its parent is the innermost span
the same thread had open; a span started on behalf of another thread's
work (a view built for step *i*) names that work in an attr such as
``view=i``. With no session the buffer is not touched: a span then
costs the annotation, one ``is_enabled()`` check and two clock reads,
a few microseconds. Counters known only once the work is done go
through :func:`annotate` behind :func:`recording`, so they are computed
only while a session collects; they live in the buffer alone.

The buffer holds one session: the first span a new session opens
empties it of the session before, once a span opened or a read of the
buffer since then saw no session collecting. Until then the last
session's records stay readable.

There is no exporter: the profiler's ``.xplane.pb`` is the export, and
:func:`recorded` reads the buffer in-process.

Span names are stable; tools that read the buffer key on them:

- training (``core/trainer.py``): ``train.fit``, ``train.view_wait``,
  ``train.backpressure``, ``train.dispatch``; the engine ``Trainer``'s
  per-view ``engine.shard`` and ``engine.stage``;
- view building (``runtime/prefetch.py``, ``core/views.py``):
  ``prefetch.build``, ``view.sample``, ``view.stage``;
- serving (``serving/server.py``): ``serve.collect``, ``serve.batch``
  and its stages ``serve.lock``, ``serve.cover``, ``serve.view``,
  ``serve.stage``, ``serve.device``, ``serve.writeback``,
  ``serve.gather``, ``serve.respond``.

Counters set by :func:`annotate`: ``steps`` on ``train.fit``,
``plan_lanes`` and ``live_edges`` on ``view.stage``, ``staged_bytes`` on
``engine.stage``, ``misses`` on ``serve.batch``.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation

CAPACITY = 65536     # records the buffer holds; later ones are dropped


class Record(NamedTuple):
    id: int
    parent: Optional[int]    # id of the innermost span open on the thread
    thread: str
    name: str
    start_ns: int            # time.perf_counter_ns()
    end_ns: int
    attrs: dict


class _Buffer:
    """The process's span records: one buffer, since the profiler
    session it mirrors is one per process too."""

    def __init__(self):
        self.records: list = []
        self.dropped = 0
        self.session = 0          # profiler sessions seen
        self._on = False          # whether the last check saw one collect
        self._lock = threading.Lock()
        self._ids = itertools.count()
        self._local = threading.local()

    def check(self) -> bool:
        """Whether a profiler session collects. The first check inside a
        new session empties the buffer of the one before."""
        on = TraceAnnotation.is_enabled()
        if on != self._on:
            with self._lock:
                if on and not self._on:
                    self.records.clear()
                    self.dropped = 0
                    self.session += 1
                self._on = on
        return on

    def stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def open(self, s: "span") -> None:
        st = self.stack()
        s._id = next(self._ids)
        s._session = self.session
        s._parent = (st[-1]._id if st and st[-1]._session == self.session
                     else None)
        st.append(s)

    def close(self, s: "span") -> None:
        st = self.stack()
        if st and st[-1] is s:
            st.pop()
        rec = Record(s._id, s._parent, threading.current_thread().name,
                     s.name, s.start_ns, s.end_ns, s.attrs)
        with self._lock:
            if s._session != self.session:
                return            # opened in a session since emptied
            if len(self.records) < CAPACITY:
                self.records.append(rec)
            else:
                self.dropped += 1


_BUFFER = _Buffer()


class span:
    """Context manager over one stage of work (see the module doc).
    ``start_ns``/``end_ns`` are set whether or not a session records,
    so a caller that keeps its own stage totals reads them from the span
    instead of timing the stage a second time."""

    __slots__ = ("name", "attrs", "start_ns", "end_ns", "_ann", "_id",
                 "_parent", "_session")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self._id = None

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        if _BUFFER.check():
            _BUFFER.open(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.perf_counter_ns()
        if self._id is not None:
            _BUFFER.close(self)
        self._ann.__exit__(*exc)
        return False

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def recording() -> bool:
    """True while a profiler session collects: the guard for attrs that
    cost anything to compute."""
    return TraceAnnotation.is_enabled()


def annotate(**attrs) -> None:
    """Add attrs to the innermost recording span of the calling thread
    (a no-op when it has none)."""
    st = getattr(_BUFFER._local, "stack", None)
    if st:
        st[-1].attrs.update(attrs)


def recorded() -> list:
    """The buffer's records, in the order their spans ended: the current
    profiler session's, or the last one's once it has ended."""
    _BUFFER.check()
    with _BUFFER._lock:
        return list(_BUFFER.records)


def dropped() -> int:
    """Records lost because the buffer held ``CAPACITY`` already."""
    return _BUFFER.dropped


def clear() -> None:
    """Empty the buffer and reset its drop count."""
    with _BUFFER._lock:
        _BUFFER.records.clear()
        _BUFFER.dropped = 0
