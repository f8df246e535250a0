"""Spans and counters of the served path, kept in memory.

A :class:`Recorder` is what ``ServeEngine.enable_tracing()`` hands to the
engine and to each of its servers.  While none is set (the default) each
span boundary on the served path costs one ``is not None`` test.

A finished span is the tuple ``(name, span_id, parent_id, job_id, start,
end, attrs)``: ``start`` and ``end`` on ``time.monotonic()``, ``attrs`` a
dict of a few small values.  The ring keeps the newest ``capacity`` spans.

Spans nest per thread: a span begun while another is open on the same
thread is its child and belongs to the same job.  A job's root span (see
:meth:`Recorder.begin_job`) takes its own span id as the job id, so every
span of one job carries that id, whichever thread recorded it.

While a profiler trace is being taken, each span begun with
:meth:`Recorder.begin` is also written as a ``jax.profiler.TraceAnnotation``
of the same name, so the trace shows it on the profiler's clock beside the
device's operations; with no trace running no annotation is made.  A job's
root span and spans recorded after the fact (:meth:`Recorder.record`) are
never written: they cross threads, or would cover every shorter span.

Counters are named integers that the served path moves up and down, read
where a span is stamped (e.g. a server's jobs in their decode phase).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

__all__ = ["Recorder", "FIELDS"]

FIELDS = ("name", "id", "parent", "job", "start", "end", "attrs")

_NAME, _ID, _PARENT, _JOB, _START, _ATTRS, _NOTE = range(7)


class Recorder:
    """A bounded ring of spans and a table of counters, shared by the
    threads of one engine."""

    def __init__(self, capacity: int = 1 << 16):
        self.spans: deque = deque(maxlen=capacity)
        self.counters: dict[str, int] = {}
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._local = threading.local()

    # -- spans ---------------------------------------------------------------
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _open(self, name: str, job: int | None, note, attrs: dict) -> list:
        stack = self._stack()
        parent = stack[-1] if stack else None
        sid = next(self._ids)
        if job is None:
            job = parent[_JOB] if parent is not None else 0
        elif job < 0:  # a job's root span
            job = sid
        if note is not None:
            note.__enter__()
        span = [name, sid, parent[_ID] if parent is not None else 0, job,
                time.monotonic(), attrs, note]
        stack.append(span)
        return span

    def begin(self, name: str, *, job: int | None = None, **attrs) -> list:
        """Open a span on this thread (and, while a profiler trace runs, its
        annotation); close it with :meth:`end`.  ``job`` defaults to the
        enclosing span's."""
        note = TraceAnnotation(name) if TraceAnnotation.is_enabled() else None
        return self._open(name, job, note, attrs)

    def begin_job(self, name: str = "job", **attrs) -> list:
        """Open a job's root span: its id is the job id of every span the
        job records.  Not written to the profiler."""
        return self._open(name, -1, None, attrs)

    def end(self, span: list) -> None:
        """Close ``span`` (and any span of this thread still open inside
        it, which an exception left behind) and keep it in the ring.  A
        span not open on this thread is left alone."""
        t = time.monotonic()
        stack = self._stack()
        if not any(s is span for s in stack):
            return
        while True:
            top = stack.pop()
            if top[_NOTE] is not None:
                top[_NOTE].__exit__(None, None, None)
            if top is span:
                break
        self.spans.append((span[_NAME], span[_ID], span[_PARENT], span[_JOB],
                           span[_START], t, span[_ATTRS]))

    def tag(self, **attrs) -> None:
        """Add attributes to the innermost span open on this thread."""
        stack = self._stack()
        if stack:
            stack[-1][_ATTRS].update(attrs)

    def record(self, name: str, start: float, end: float, *,
               job: int | None = None, parent: int | None = None,
               **attrs) -> None:
        """Keep a span whose bounds were stamped elsewhere (``start ==
        end`` for an instant).  Job and parent default to the innermost
        span open on this thread."""
        stack = self._stack()
        top = stack[-1] if stack else None
        if job is None:
            job = top[_JOB] if top is not None else 0
        if parent is None:
            parent = top[_ID] if top is not None else 0
        self.spans.append((name, next(self._ids), parent, job, start, end,
                           attrs))

    def current_job(self) -> int:
        """Job id of the innermost span open on this thread (0: none)."""
        stack = self._stack()
        return stack[-1][_JOB] if stack else 0

    # -- counters ------------------------------------------------------------
    def add(self, name: str, delta: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + delta

    def count(self, name: str) -> int:
        return self.counters.get(name, 0)
